"""The readers of the program's own spans and scopes: ``ref_train_ms``,
``codec_ms``, ``fetch_stall_ms`` and ``host_stall_ms`` on hand-built
records with known answers; ``FLServer.run_round``'s host spans as the
CPU profiler records them; and one round of a TPU v5e trace of
``femnist_cnn.topk_all`` recorded by the benchmark
(data/trace_femnist_topk_round.json.gz)."""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import trace
from bench.spec import reader

DATA = Path(__file__).parent / "data"
NEW = ("ref_train_ms", "codec_ms", "fetch_stall_ms", "host_stall_ms")


def _read(name, record, rounds=1):
    return reader(name)(SimpleNamespace(reduced=trace.Reduced(record, rounds)))


def _record(devices, host, op_names=None):
    return {"devices": devices, "host": host, "op_names": op_names or {},
            "kernel_bytes": {}}


ROUND = [["bench.round", 0.0, 100.0], ["round", 0.0, 100.0],
         ["host.dispatch", 0.0, 10.0], ["host.fetch", 10.0, 50.0],
         ["host.fetch", 60.0, 10.0], ["host.account", 70.0, 20.0]]


def test_stall_readers_split_idle_time_by_host_span():
    """Device 0 is busy in [5, 55), device 1 in [0, 70): inside the
    fetches [10, 70) device 0 idles 15 ns and device 1 none; inside the
    dispatch [0, 10) and the accounting [70, 90) device 0 idles 5 + 20
    and device 1 20."""
    rec = _record([[["while.1", 5.0, 50.0]], [["while.1", 0.0, 70.0]]],
                  ROUND)
    assert _read("fetch_stall_ms", rec) == pytest.approx(7.5e-6)
    assert _read("host_stall_ms", rec) == pytest.approx(22.5e-6)
    assert _read("host_stall_ms", rec, rounds=5) == pytest.approx(4.5e-6)


def test_scope_readers_and_aggregate_share_the_nested_scopes():
    ops = [["while.1", 0.0, 40.0], ["fusion.2", 40.0, 10.0],
           ["fusion.3", 50.0, 5.0], ["fusion.4", 55.0, 20.0],
           ["fusion.5", 75.0, 5.0]]
    names = {
        "while.1": "jit(round_step)/round.train/vmap(jit(local_train))/while",
        "fusion.2": "jit(round_step)/round.compress/jit(topk_mask)/top_k",
        "fusion.3": "jit(round_step)/round.aggregate/edge_codec/"
                    "jit(topk_mask)/top_k",
        "fusion.4": "jit(round_step)/round.aggregate/ref_train/vmap(jit("
                    "local_train))/while",
        "fusion.5": "jit(round_step)/round.aggregate/dot_general"}
    rec = _record([ops], ROUND, names)
    assert _read("ref_train_ms", rec, rounds=2) == pytest.approx(10e-6)
    assert _read("codec_ms", rec, rounds=2) == pytest.approx(7.5e-6)
    assert _read("aggregate_ms", rec, rounds=2) == pytest.approx(15e-6)


def test_readers_are_silent_without_their_spans_or_scopes():
    """The parent's program opens none of these, so nothing is read."""
    names = {"while.1": "jit(round_step)/round.aggregate/while"}
    rec = _record([[["while.1", 0.0, 40.0]]], [["bench.round", 0.0, 100.0]],
                  names)
    for name in NEW:
        assert _read(name, rec) is None, name
    assert _read("aggregate_ms", rec) == pytest.approx(40e-6)


def test_run_round_spans_on_the_profiler_clock(tmp_path):
    """A round on the CPU under the profiler, telemetry off: ``round``
    encloses ``host.dispatch``, the reads' ``host.fetch`` spans and
    ``host.account``, on the thread that drew ``bench.round``."""
    import jax

    from repro.configs.base import FLConfig
    from repro.federated import FLServer, make_data, make_topology

    fl = FLConfig(n_clouds=3, clients_per_cloud=3, clients_per_round=4,
                  local_epochs=1, local_batch=4, ref_samples=12)
    data = make_data(fl, "cifar10", seed=0, n_samples=300,
                     samples_per_client=8)
    server = FLServer(fl, make_topology(fl), data, method="cost_trustfl",
                      seed=0, engine="jit")
    server.run_round(0)                      # compiles outside the trace
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace.WINDOW_MARK):
            server.run_round(1)
    host = [(n, s, s + d)
            for n, s, d in trace.record_from_xplane(str(tmp_path), [])["host"]]
    (mark,) = [e for e in host if e[0] == trace.WINDOW_MARK]
    (rnd,) = [e for e in host if e[0] == "round"]
    assert mark[1] <= rnd[1] and rnd[2] <= mark[2]
    inside = {n: [e for e in host if e[0] == n
                  and rnd[1] <= e[1] and e[2] <= rnd[2]]
              for n in ("host.dispatch", "host.fetch", "host.account")}
    assert len(inside["host.dispatch"]) == 1
    assert len(inside["host.fetch"]) == 2   # delivered mask, reputations
    assert len(inside["host.account"]) == 1
    order = sorted((s, n) for n, evs in inside.items() for _, s, _ in evs)
    assert [n for _, n in order] == ["host.dispatch", "host.fetch",
                                     "host.fetch", "host.account"]


def test_recorded_topk_round():
    """One round of the top-k cell on the chip: the reference trainings
    took 10.17 ms, the codec 31.78 (26.51 client, 5.27 edge), and of the
    4.32 ms the device idled, 3.62 fell in the two reads (the mask read
    returns 2.85 ms after the step's last op) and 0.51 in the dispatch and
    the accounting. Host spans and device ops share one clock: the step
    runs after its dispatch begins and before the last read ends."""
    with gzip.open(DATA / "trace_femnist_topk_round.json.gz") as f:
        record = json.load(f)
    readings = {"ref_train_ms": 10.1705, "codec_ms": 31.7784,
                "fetch_stall_ms": 3.6217, "host_stall_ms": 0.5146,
                "aggregate_ms": 15.8823}
    for name, value in readings.items():
        assert _read(name, record) == pytest.approx(value, abs=1e-3), name
    host = {n: [(s, s + d) for m, s, d in record["host"] if m == n]
            for n in ("host.dispatch", "host.fetch", "host.account")}
    assert [len(v) for v in host.values()] == [1, 2, 1]
    ops = record["devices"][0]
    assert min(s for _, s, _ in ops) >= host["host.dispatch"][0][0]
    assert max(s + d for _, s, d in ops) <= host["host.fetch"][-1][1]


def test_hlo_same_takes_out_only_source_names():
    """bench/hlo_same.py compares programs with their op_name metadata
    and file tables taken out, and nothing else."""
    from bench.hlo_same import normalise
    hlo = ('HloModule m\n\nFileNames\n1 "/a/engine.py"\n\nStackFrames\n'
           '1 {file_location_id=1}\n\n'
           '  %add.1 = f32[4]{0} add(%a, %b), metadata={op_name='
           '"jit(round_step)/round.aggregate/ref_train/add" '
           'source_file="/a/engine.py" source_line=708}\n')
    moved = hlo.replace("/a/", "/b/").replace("ref_train/", "")
    assert normalise(hlo) == normalise(moved.replace("=708", "=705"))
    assert normalise(hlo) != normalise(
        hlo.replace("add(%a, %b)", "multiply(%a, %b)"))
