"""The trace reduction (bench.trace) on hand-built records with known
answers, and on one round of a TPU v5e trace of ``cifar10_cnn.paper``
recorded by the benchmark (data/trace_cifar10_paper_round.json.gz)."""
import gzip
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).parent / "data"


def _record(device_ops, host=None, op_names=None, kernel_bytes=None):
    return {"devices": device_ops,
            "host": host if host is not None else [["bench.round", 0.0, 100.0]],
            "op_names": op_names or {}, "kernel_bytes": kernel_bytes or {}}


def test_nesting_busy_and_phases():
    ops = [["while.1", 10.0, 50.0],      # [10, 60) train, encloses two
           ["fusion.2", 12.0, 10.0],
           ["fusion.3", 30.0, 20.0],
           ["fusion.4", 70.0, 10.0],     # [70, 80) aggregate
           ["copy.5", 75.0, 30.0]]       # [75, 105) clipped to 100
    names = {"while.1": "jit(round_step)/round.train/while",
             "fusion.4": "jit(round_step)/round.aggregate/add"}
    r = trace.Reduced(_record([ops], op_names=names), rounds=2)
    assert r.busy_ns() == pytest.approx(50.0 + 30.0)     # [10,60) + [70,100)
    phases = r.phase_ms_per_round()
    assert phases["train"] == pytest.approx(50.0 / 1e6 / 2)
    assert phases["aggregate"] == pytest.approx(10.0 / 1e6 / 2)
    assert phases["other"] == pytest.approx(25.0 / 1e6 / 2)
    assert [n for n, _ in r.top_ops()][0] == "while.1 [train]"


def test_idle_gap_named_by_innermost_host_event():
    host = [["bench.round", 0.0, 100.0], ["run_round", 0.0, 100.0],
            ["_value", 40.0, 20.0]]
    r = trace.Reduced(_record([[["a", 0.0, 40.0], ["b", 60.0, 40.0]]],
                              host=host), rounds=1)
    assert r.idle_gaps() == [["_value", pytest.approx(20e-9)]]


def test_collective_exposed_and_kernel_bytes():
    dev = [["all-reduce.1", 0.0, 10.0],       # half hidden by compute
           ["fusion.1", 5.0, 10.0],
           ["custom-call.7", 20.0, 4.0]]
    names = {"custom-call.7": "jit(f)/jit(topk_mask)/pallas_call"}
    r = trace.Reduced(_record([dev, dev], op_names=names,
                              kernel_bytes={"custom-call.7": 1000}), rounds=1)
    assert r.collective_exposed_ms_per_round() == pytest.approx(5.0 / 1e6)
    secs, nbytes = r.kernel_calls(r"topk_mask\)/.*pallas_call")
    assert (secs, nbytes) == (pytest.approx(8e-9), 2000)
    none = trace.Reduced(_record([[["fusion.1", 0.0, 1.0]]]), rounds=1)
    assert none.collective_exposed_ms_per_round() is None
    assert none.matching_ms_per_round("top_k") is None


def test_instruction_and_hlo_index():
    assert trace.instruction("%while.20 = (s32[]) while(%t), body=%b") == "while.20"
    hlo = ('  %branch_0_fun.2 = f32[32,428544]{1,0} custom-call(%a, %b), '
           'custom_call_target="tpu_custom_call", operand_layout_constraints='
           '{f32[32,428544]{1,0}, f32[32,1]{1,0}}, frontend_attributes={}, '
           'metadata={op_name="jit(round_step)/round.compress/jit(topk_mask)/'
           'cond/branch_0_fun/pallas_call"}')
    names, kbytes = trace.hlo_index([hlo])
    assert names["branch_0_fun.2"].endswith("pallas_call")
    assert kbytes["branch_0_fun.2"] == 2 * 32 * 428544 * 4 + 32 * 4


def test_recorded_paper_round():
    """One round of the paper cell on the chip: the device's own step
    line read 517.8 ms busy of a 522.0 ms round, the client-training loop
    489.8 ms and the reference-training loop 26.6 ms."""
    with gzip.open(DATA / "trace_cifar10_paper_round.json.gz") as f:
        record = json.load(f)
    r = trace.Reduced(record, rounds=1)
    assert r.window_ns / 1e6 == pytest.approx(522.03, abs=0.01)
    assert 1.0 - r.busy_ns() / r.window_ns == pytest.approx(0.0081, abs=0.001)
    phases = r.phase_ms_per_round()
    assert phases["train"] == pytest.approx(490.03, abs=0.5)
    assert phases["aggregate"] == pytest.approx(26.75, abs=0.5)
    assert r.collective_exposed_ms_per_round() is None
