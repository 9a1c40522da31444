"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the fleet's data from the seed, constructs ``FLServer``
(``engine="auto"``; weights from the seed) and drives it by its own
``run_round`` through the rounds the correctness check compares, then
one more, so that nothing compiles later. With ``--trace 0`` the window
calls ``run_round(t)`` back to back for ``--seconds`` and reports the
cell's end-to-end metrics; with ``--trace 1`` it runs a few rounds under
the profiler and reports the per-layer metrics. Then the program's state
is freed and the plain reference replays the compared rounds from the
seed (``bench.compare``).

The last line of stdout is one JSON object. Off a TPU, or with fewer
chips than the cell asks for, the run exits 2 and prints no result.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

COMPARED_ROUNDS = 3     # rounds the reference replays
TRACE_ROUNDS = 5        # rounds under the profiler with --trace 1
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


class NoChip(RuntimeError):
    pass


@dataclass
class ReadContext:
    """What a per-layer reader (``bench/metrics/<name>.py``) sees."""
    reduced: Any                 # bench.trace.Reduced of the traced window
    rounds: int                  # rounds in the traced window
    window_s: float              # its length on the host clock
    chips: int
    peaks: Dict[str, float]
    model_flops_per_round: int


def setup_jax() -> None:
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def check_chips(chips: int) -> None:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")


class CompileCounter:
    """Counts backend compilations (from JAX's monitoring events)."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.count += 1


def step_hlo(server, t: int) -> str:
    """Compiled HLO text of the round step the server runs (served from
    the compile cache). The mesh engine's ``step`` wraps its jitted
    program, which is then found among the wrapper's free variables."""
    import jax.numpy as jnp
    step = server._eng.step
    args = (server._eng_state, server._eng_data)
    if hasattr(step, "lower"):
        return step.lower(*args, t).compile().as_text()
    for cell in step.__closure__ or ():
        if hasattr(cell.cell_contents, "lower"):
            return cell.cell_contents.lower(
                *args, jnp.asarray(t, jnp.int32)).compile().as_text()
    raise TypeError(f"no jitted program behind {step!r}")


def p90(values: List[float]) -> float:
    return statistics.quantiles(values, n=10)[-1]


def _round(server, t: int) -> int:
    """One round; 1 when its dollars or reputations are not finite."""
    import numpy as np
    m = server.run_round(t)
    return int(not (np.isfinite(m.cost) and np.all(np.isfinite(m.reputation))))


def timed_window(server, t: int, seconds: float) -> Tuple[Dict, int, int]:
    """``run_round`` back to back for ``seconds`` (and at least two
    rounds): (metrics without ``setup_s``, rounds, failed rounds)."""
    import jax
    times: List[float] = []
    failed = 0
    start = end = time.perf_counter()
    while end - start < seconds or len(times) < 2:
        a = time.perf_counter()
        failed += _round(server, t + len(times))
        end = time.perf_counter()
        times.append(end - a)
    jax.block_until_ready(server.params)
    window = time.perf_counter() - start
    print(f"window: {len(times)} rounds in {window:.4f} s; round ms min "
          f"{1e3 * min(times):.3f} median {1e3 * statistics.median(times):.3f} "
          f"max {1e3 * max(times):.3f}", file=sys.stderr)
    print("round ms: " + " ".join(f"{1e3 * x:.2f}" for x in times),
          file=sys.stderr)
    return ({"rounds_per_s": len(times) / window,
             "round_ms_p90": 1000.0 * p90(times)}, len(times), failed)


def traced_window(server, t: int, cell, job, hlo: str
                  ) -> Tuple[Dict, Dict, Dict, int]:
    """``TRACE_ROUNDS`` rounds under the profiler, reduced to the cell's
    per-layer metrics: (metrics, breakdown, busy/window seconds, failed
    rounds)."""
    import jax

    from bench import trace as trace_mod
    from bench.roofline import peaks_for, round_model_flops
    from bench.spec import reader

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(str(TRACE_DIR))
    failed = 0
    for i in range(TRACE_ROUNDS):
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_MARK):
            failed += _round(server, t + i)
    jax.block_until_ready(server.params)
    jax.profiler.stop_trace()
    record = trace_mod.record_from_xplane(str(TRACE_DIR), [hlo])
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    reduced = trace_mod.Reduced(record, TRACE_ROUNDS)
    data = cell.config["data"]
    ctx = ReadContext(
        reduced=reduced, rounds=TRACE_ROUNDS, window_s=reduced.window_ns / 1e9,
        chips=cell.chips, peaks=peaks_for(jax.devices()[0].device_kind),
        model_flops_per_round=round_model_flops(
            job, data["samples_per_client"], data["ref_samples"]))
    metrics = {}
    for e in cell.per_layer:
        value = reader(e["name"])(ctx)
        if value is not None:
            metrics[e["name"]] = {"value": value, "unit": e["unit"]}
    breakdown = {"device_ops": reduced.top_ops(),
                 "idle_gaps": reduced.idle_gaps()}
    busy = {"busy_s": reduced.busy_ns() / 1e9,
            "window_s": reduced.window_ns / 1e9}
    return metrics, breakdown, busy, failed


def run_cell(cell, seed: int, seconds: float, trace: bool,
             require_chip: bool = True) -> Dict[str, Any]:
    """Set-up, one window, then the reference replay and the verdict."""
    import jax

    from bench import compare, reference, system

    if require_chip:
        check_chips(cell.chips)
    compiles = CompileCounter()
    pseed = system.program_seed(seed)
    job = system.make_job(cell.config, cell.traffic)
    data = system.make_data(cell.config, job, pseed)
    server = system.build_server(cell.config, cell.traffic, data, pseed)
    if server.d_params != job.d_params:
        raise ValueError(f"program's D={server.d_params} differs from the "
                         f"configuration's D={job.d_params}")
    engine = server.engine_resolved
    prog = system.first_rounds(server, COMPARED_ROUNDS)
    t = COMPARED_ROUNDS
    server.run_round(t)                    # served without compiling
    t += 1
    jax.block_until_ready(server.params)
    setup_s = time.perf_counter() - _T_START

    result: Dict[str, Any] = {}
    if trace:
        hlo = step_hlo(server, t)          # what the trace's ops are named by
        compiles_before = compiles.count
        metrics, breakdown, busy, failed = traced_window(server, t, cell, job,
                                                         hlo)
        attempted = TRACE_ROUNDS
        result["breakdown"] = breakdown
    else:
        compiles_before = compiles.count
        values, attempted, failed = timed_window(server, t, seconds)
        values["setup_s"] = setup_s
        metrics = {e["name"]: {"value": values[e["name"]], "unit": e["unit"]}
                   for e in cell.end_to_end}
        busy = {}
    compiled_in_window = compiles.count - compiles_before
    device = dict(system.device_summary(cell.chips), **busy)

    # free the program's state before the reference runs
    del server
    gc.collect()
    t_ref = time.perf_counter()
    malicious = reference.draw_malicious(job, pseed)
    y = reference.poison_labels(job, data.client_y, malicious, pseed)
    ref = reference.run_rounds(
        job, pseed, data.client_x, y, data.ref_x, data.ref_y, malicious,
        COMPARED_ROUNDS,
        precision=reference.PRECISIONS[cell.config["matmul_precision"]])
    print(f"reference: {time.perf_counter() - t_ref:.3f} s", file=sys.stderr)
    nums = compare.numbers(prog, ref, compressed=job.codec("intra") != "none")
    nums["compiles_in_window"] = float(compiled_in_window)
    correct = compare.verdict(nums, cell.limits) and failed == 0
    checks = {k: {"value": nums.get(k), "limit": lim}
              for k, lim in cell.limits.items()}
    return {"correct": bool(correct), "engine": engine,
            "attempted": attempted, "failed": failed, "metrics": metrics,
            "device": device, **result, "checks": checks}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.spec import resolve
    cell = resolve(args.workload)
    setup_jax()
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
