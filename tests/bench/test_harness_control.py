"""The correctness check's control at a small size on the CPU: the
reference computed in bfloat16 throughout, and each planted fault (a
minibatch halved, an error-feedback residual never added back, a state
left unchanged), put in the program's place or planted in it, must fail
the cell's limits; the program itself must pass them (bench.calibrate,
which reads the same numbers on the chip at the cell's own size)."""
import pytest

from bench import calibrate, compare
from bench.spec import ROOT, load_json

CELLS = [w["name"] for w in load_json(ROOT / "BENCHMARK.json")["workloads"]
         if w["chips"] == 1]


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_and_program_passes(tiny_cell, name):
    cell = tiny_cell(name)
    # the readings compare outputs; compilation is the window's concern
    limits = {k: v for k, v in cell.limits.items() if k != "compiles_in_window"}
    rows = {r.pop("reading"): r for r in calibrate.readings(cell, 2 ** 31 + 99)}
    for row in rows.values():
        for key in [k for k in row
                    if k in ("seed", "precision")
                    or k.endswith(("_later", "_leaves"))]:
            row.pop(key)
    assert compare.verdict(rows.pop("program"), limits)
    assert {"control", "half_batch", "half_batch_clients",
            "program_half_batch_clients", "unchanged_state"} <= set(rows)
    for reading, row in rows.items():
        assert not compare.verdict(row, limits), (reading, row)
