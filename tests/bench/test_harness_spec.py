"""BENCHMARK.json against the rules of its format, and the resolution of
its cells, configurations, traffic mixes and metrics to files by name."""
import json
import re
import shutil

import pytest

from bench import spec
from bench.spec import ROOT, load_json

BENCH = load_json(ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all(not p.startswith("/") and ".." not in p for p in BENCH["paths"])
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_units_and_lines():
    entries = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
               + BENCH["per_layer"])
    assert len({e["name"] for e in entries}) == len(entries)
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key], e
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


def test_configs_used_and_cells_unique():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    cell = spec.resolve(name)
    assert cell.config["d_params"] > 0 and "why" in cell.traffic
    assert {"mask_diff", "wire_diff", "update_gap"} <= set(cell.limits)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "rounds_per_s"}
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(spec.reader(m["name"]))
        assert m["moves"] in {e["name"] for e in cell.end_to_end}


def test_every_reader_is_named_in_the_benchmark():
    files = {p.stem for p in (ROOT / "bench" / "metrics").glob("*.py")}
    assert files == {m["name"] for m in BENCH["per_layer"]}


def test_new_cell_and_metric_are_picked_up_from_files(tmp_path):
    """A later change adds a traffic mix, a cell and a metric as new files
    and entries; nothing in the harness is edited."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    first = bench["workloads"][0]
    traffic = load_json(ROOT / "bench" / "traffic" / f"{first['traffic']}.json")
    traffic["local_epochs"] = 1
    (tmp_path / "bench" / "traffic" / "one_epoch.json").write_text(json.dumps(traffic))
    new = f"{first['config']}.one_epoch"
    shutil.copy(ROOT / "bench" / "limits" / f"{first['name']}.json",
                tmp_path / "bench" / "limits" / f"{new}.json")
    (tmp_path / "bench" / "metrics" / "rounds_traced.py").write_text(
        "def read(ctx):\n    return float(ctx.rounds)\n")
    bench["workloads"].append(dict(first, name=new, traffic="one_epoch"))
    bench["per_layer"].append({"name": "rounds_traced", "unit": "rounds",
                               "better": "higher", "source": "program_counter",
                               "layer": "device", "moves": "rounds_per_s",
                               "workloads": [new]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.resolve(new, root=tmp_path)
    assert cell.traffic["local_epochs"] == 1
    assert [m["name"] for m in cell.per_layer] == ["rounds_traced"]
    read = spec.reader("rounds_traced", root=tmp_path)
    assert read(type("Ctx", (), {"rounds": 5})()) == 5.0
    with pytest.raises(KeyError):
        spec.resolve("no_such.cell", root=tmp_path)


def test_every_traffic_key_reaches_the_program(monkeypatch, tiny_cell):
    """A traffic mix configures the program through any field of its
    FLConfig, with no list in the harness to extend."""
    from repro import federated

    from bench import system
    seen = {}
    monkeypatch.setattr(federated, "FLServer",
                        lambda flcfg, *a, **kw: seen.setdefault("cfg", flcfg))
    cell = tiny_cell(CELLS[0])
    job = system.make_job(cell.config, cell.traffic)
    data = system.make_data(cell.config, job, 7)
    system.build_server(cell.config, dict(cell.traffic, qsgd_levels=7), data, 7)
    assert seen["cfg"].qsgd_levels == 7
    with pytest.raises(TypeError):
        system.build_server(cell.config, dict(cell.traffic, no_such_key=1),
                            data, 7)


def test_reference_refuses_what_it_does_not_model():
    from bench import system
    cell = spec.resolve(CELLS[0])
    with pytest.raises(ValueError, match="does not model.*qsgd_levels"):
        system.make_job(cell.config, dict(cell.traffic, qsgd_levels=7))
    with pytest.raises(ValueError, match="cost_trustfl"):
        system.make_job(cell.config, dict(cell.traffic, method="fedavg"))
