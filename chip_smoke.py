"""Smoke run of the Cost-TrustFL round engine on one TPU chip.

Drives the main path a user calls, ``run_simulation`` -> ``FLServer`` ->
``engine="auto"`` -> the ``lax.scan`` round engine, at the paper's
published sizes (the ``FLConfig`` defaults: 3 clouds x 30 clients, 30
selected per round, 5 local epochs at batch 32, the CIFAR-10-shaped CNN
on 32x32x3 inputs with D = 545,098 parameters, ``make_data`` defaults of
12,000 samples and 96 per client). Data and weights come from seeds.

Phases, each of which ends the process with exit code 1 if it fails:

* kernels   -- ``topk_mask`` and ``stochastic_quantize`` from
  ``repro.kernels.ops`` at (30, 545,098), compared with the
  ``repro.kernels.ref`` oracles run on the same chip;
* rounds    -- three rounds of ``cost_trustfl`` under ``label_flip`` for
  each compressor (``none``, ``topk``, ``qsgd``; link policy
  ``cross_only``): routed to the scan engine, finite results, accuracy
  in [0, 1], $ and bytes equal to the float64 host accounting, and a
  Pallas kernel (``tpu_custom_call``) in the compiled ``topk`` and
  ``qsgd`` round steps;
* reference -- round 0 of the ``none`` config once more on the chip and
  on the host CPU in this process: masks, bytes and $ equal, reputation
  and parameters within the bounds stated at ``REP_RTOL``/``DELTA_RTOL``.

``--chips 4`` runs only the mesh path: the sharded engine over a (4, 1)
("cloud", "client") mesh, one cloud per chip, against the scan engine
on chip 0 (4 clouds x 32 clients, all 128 selected, ``sign_flip``).

The persistent compile cache goes where ``repro.launch.cache`` says.
The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; nothing is
printed there unless every phase passed on a TPU.

    python chip_smoke.py
    python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import replace
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
# the reference phase runs on the host CPU backend beside the chip; a
# platform list that leaves it out gets it appended (the default stays
# the first entry)
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import FLConfig  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402

# -- tolerances -------------------------------------------------------------
# stochastic_quantize: the kernel (Mosaic) and the oracle (XLA) each
# divide x by its row scale; a quotient that rounds to the other side of
# an integer boundary moves floor() by one level. So every level may
# differ by at most 1, and only on a sliver of entries.
QSGD_MAX_LEVEL_DIFF = 1
QSGD_MAX_DIFF_FRAC = 1e-4
# chip vs CPU, round 0: the chip runs f32 matmuls as one bf16 pass (8-bit
# mantissa, f32 accumulation); the CPU runs them in f32. Through fifteen
# local SGD steps (ReLU and max-pool decisions flip) that moves each
# client update by about a tenth: a CPU run whose matmul operands are
# rounded to bf16 differs from the f32 CPU run by 0.105-0.108 in the
# round's parameter delta (relative L2) and by up to 1.8e-2 in reputation
# (of its maximum), at the CNN's full width with 3 x 4 to 3 x 10 clients.
# The bounds allow about 3x that; an update computed from the wrong data
# or clients is uncorrelated with the reference, a relative L2 of 1 or
# more.
DELTA_RTOL = 0.3
REP_RTOL = 5e-2
# sharded vs scan engine on the chip, three rounds: the two programs tile
# and associate f32 sums differently (per-device batches of 32 clients vs
# 128 on one chip, psum vs one reduction), so they differ in last bits
# from round 0 on. Three rounds of local training amplify that: a
# one-ulp change of every client input moves the final reputation by
# 2.4e-2 of its maximum in f32 and by 4.7e-2 with bf16-rounded matmul
# operands (CPU, this config with 4 x 8 clients). On the CPU the two
# engines agree to the last bit, hence the 1e-4 of tests/test_sharded.py;
# on the chip the bound is about twice the bf16 figure.
MESH_REP_RTOL = 0.1

ROUNDS = 3
METHOD = "cost_trustfl"


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# kernels

def kernel_phase(m: int = 30, d: int = 545_098) -> None:
    from repro.compress import TopKCodec, QSGDCodec
    from repro.kernels import ops, ref

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    g = jax.random.normal(k1, (m, d), jnp.float32)

    k = TopKCodec().k_for(d)
    out = np.asarray(ops.topk_mask(g, k=k))
    thr = jax.lax.top_k(jnp.abs(g), k)[0][:, -1]
    want = np.asarray(ref.topk_mask_ref(g, thr))
    check(np.array_equal(out, want), "topk_mask differs from its oracle")
    # ties at the threshold are kept, so a row may keep a few more than k
    kept = (out != 0).sum(axis=1)
    check(np.all(kept >= k), f"topk_mask kept {kept.min()} entries in a "
                             f"row, expected at least {k}")
    log(f"kernel topk_mask ({m}, {d}) k={k}: equal to oracle, "
        f"{int(kept.max() - k)} tied entries kept beyond k at most")

    levels = QSGDCodec().levels
    u = jax.random.uniform(k2, (m, d))
    scale = jnp.max(jnp.abs(g), axis=1)
    q = np.asarray(ops.stochastic_quantize(g, scale, u, levels=levels))
    q_ref = np.asarray(ref.stochastic_quantize_ref(g, scale, u, levels))
    diff = np.abs(q.astype(np.int64) - q_ref)
    frac = float(np.mean(diff > 0))
    check(int(diff.max()) <= QSGD_MAX_LEVEL_DIFF,
          f"stochastic_quantize off by {int(diff.max())} levels")
    check(frac <= QSGD_MAX_DIFF_FRAC,
          f"stochastic_quantize differs on {frac:.2e} of entries")
    check(int(np.abs(q).max()) <= levels, "quantized level out of range")
    deq = np.asarray(ref.dequantize_ref(jnp.asarray(q), scale, levels))
    step = np.asarray(scale)[:, None] / levels
    check(np.all(np.abs(deq - np.asarray(g)) <= step * (1 + 1e-6)),
          "dequantized error exceeds one quantization step")
    log(f"kernel stochastic_quantize ({m}, {d}) L={levels}: max level diff "
        f"{int(diff.max())}, differing fraction {frac:.3e} "
        f"(bound {QSGD_MAX_LEVEL_DIFF} level on <= {QSGD_MAX_DIFF_FRAC})")


# ---------------------------------------------------------------------------
# rounds through run_simulation

class _Run:
    """One ``run_simulation`` call and the telemetry it emitted."""

    def __init__(self, flcfg: FLConfig, data, rounds: int, engine: str):
        from repro.federated import run_simulation
        from repro.telemetry import ListSink, Telemetry

        sink = ListSink()
        self.result = run_simulation(flcfg, method=METHOD, rounds=rounds,
                                     eval_every=rounds, data=data,
                                     engine=engine,
                                     telemetry=Telemetry(sink))
        ev = sink.events
        # the server records its resolved engine
        # (FLServer.engine_resolved) in every event it emits
        self.engine = next(e["engine"] for e in ev
                           if e["event"] == "run_start")
        self.rounds = [e for e in ev if e["event"] == "round"]
        spans = [e for e in ev if e["event"] == "span" and e["name"] == "round"]
        self.first_s = next(e["seconds"] for e in spans
                            if e["phase"] == "compile+execute")
        steady = [e["seconds"] for e in spans if e["phase"] == "execute"]
        self.steady_s = float(np.median(steady)) if steady else float("nan")


def _check_run(run: _Run, tag: str, rounds: int) -> None:
    r = run.result
    check(len(run.rounds) == rounds, f"{tag}: {len(run.rounds)} round events")
    acc = r.final_accuracy
    check(acc is not None and np.isfinite(acc) and 0.0 <= acc <= 1.0,
          f"{tag}: accuracy {acc}")
    check(np.all(np.isfinite(r.reputation)), f"{tag}: reputation not finite")
    for ev in run.rounds:
        check(np.isfinite(ev["digest"]["params_l2"]),
              f"{tag}: params not finite after round {ev['t']}")
    # SimResult totals (FLServer's float64 host accounting per round) vs
    # the float64 CostModel pass telemetry makes over each round's
    # delivered mask
    for key, total in (("cost", r.total_cost), ("intra_bytes", r.intra_bytes),
                       ("cross_bytes", r.cross_bytes)):
        acc64 = float(np.sum([ev[key] for ev in run.rounds],
                             dtype=np.float64))
        check(np.isfinite(total) and total == acc64,
              f"{tag}: {key} total {total!r} != host accounting {acc64!r}")
        check(total > 0, f"{tag}: {key} total is {total!r}")


def _step_hlo(flcfg: FLConfig, data) -> str:
    """Compiled HLO of the scan engine's round step for ``flcfg`` (the
    server's own executable: engines are cached per static config)."""
    from repro.federated import engine as engine_mod
    from repro.federated.simulation import make_topology

    topo = make_topology(flcfg)
    static = engine_mod.static_from(flcfg, topo, METHOD,
                                    input_shape=data.client_x.shape[2:],
                                    n_classes=data.n_classes)
    eng = engine_mod.compiled(static)
    dev = engine_mod.make_client_data(flcfg, topo, data, 0)
    return eng.step.lower(eng.init_state(0), dev, 0).compile().as_text()


def round_phase(base: FLConfig, data) -> None:
    for comp in ("none", "topk", "qsgd"):
        flcfg = replace(base, compressor=comp, link_policy="cross_only")
        run = _Run(flcfg, data, ROUNDS, engine="auto")
        tag = f"round[{comp}]"
        check(run.engine == "jit", f"{tag}: engine resolved to {run.engine!r}")
        _check_run(run, tag, ROUNDS)
        if comp != "none":
            check("tpu_custom_call" in _step_hlo(flcfg, data),
                  f"{tag}: no Pallas kernel in the compiled round step")
        r = run.result
        log(f"{tag}: engine={run.engine} first round (compile+execute) "
            f"{run.first_s:.3f}s, so compile ~{run.first_s - run.steady_s:.3f}"
            f"s; steady {run.steady_s:.4f}s/round, "
            f"acc={r.final_accuracy:.4f} cost=${r.total_cost:.6f} "
            f"intra={r.intra_bytes:.0f}B cross={r.cross_bytes:.0f}B"
            + (", tpu_custom_call in step" if comp != "none" else ""))


# ---------------------------------------------------------------------------
# chip vs host CPU, round 0

def _round0(flcfg: FLConfig, data):
    from repro.federated import FLServer
    from repro.federated.simulation import make_topology

    server = FLServer(flcfg, make_topology(flcfg), data, method=METHOD,
                      seed=0)
    check(server.engine_resolved == "jit",
          f"reference: engine resolved to {server.engine_resolved!r}")
    params0 = jax.tree.map(np.asarray, server.params)
    metrics = server.run_round(0)
    params1 = jax.tree.map(np.asarray, server.params)
    delta = np.concatenate([(params0[k] - params1[k]).ravel()
                            for k in sorted(params0)])
    return metrics, delta


def reference_phase(base: FLConfig, data) -> None:
    flcfg = replace(base, compressor="none")
    m_chip, d_chip = _round0(flcfg, data)
    with jax.default_device(jax.devices("cpu")[0]):
        m_cpu, d_cpu = _round0(flcfg, data)
    rep_dev = float(np.max(np.abs(m_chip.reputation - m_cpu.reputation))
                    / np.max(np.abs(m_cpu.reputation)))
    delta_dev = float(np.linalg.norm(d_chip - d_cpu)
                      / np.linalg.norm(d_cpu))
    log(f"reference round 0 chip vs cpu: reputation max dev {rep_dev:.3e} "
        f"of max (bound {REP_RTOL}); param delta rel L2 dev "
        f"{delta_dev:.3e} (bound {DELTA_RTOL})")
    check(np.array_equal(m_chip.selected, m_cpu.selected),
          "reference: delivered masks differ")
    check(m_chip.cost == m_cpu.cost, "reference: $ differs")
    for key in ("intra_bytes", "cross_bytes"):
        check(m_chip.extra[key] == m_cpu.extra[key],
              f"reference: {key} differ")
    check(np.isfinite(rep_dev) and rep_dev <= REP_RTOL,
          f"reference: reputation deviation {rep_dev:.3e} > {REP_RTOL}")
    check(np.isfinite(delta_dev) and delta_dev <= DELTA_RTOL,
          f"reference: parameter-delta deviation {delta_dev:.3e} > "
          f"{DELTA_RTOL}")
    log("reference round 0 chip vs cpu: masks, bytes and $ equal")


# ---------------------------------------------------------------------------
# four chips: sharded engine vs scan engine

MESH_CONFIG = FLConfig(n_clouds=4, clients_per_cloud=32, clients_per_round=128,
                       attack="sign_flip")


def mesh_phase() -> None:
    from repro.federated import engine as engine_mod
    from repro.federated import make_data
    from repro.federated import sharded as sharded_mod
    from repro.federated.simulation import make_topology

    flcfg, rounds = MESH_CONFIG, ROUNDS
    topo = make_topology(flcfg)
    n_dev = len(jax.devices())
    route = engine_mod.resolve_engine("auto", flcfg, topo, METHOD)
    check(route == "shard", f"mesh: auto routed to {route!r}")
    axes = sharded_mod.mesh_axes(flcfg.n_clouds, topo.n_clients)
    check(axes == (n_dev, 1), f"mesh: axes {axes}, want ({n_dev}, 1)")
    data = make_data(flcfg)

    scan = _Run(flcfg, data, rounds, engine="jit")      # chip 0
    shard = _Run(flcfg, data, rounds, engine="auto")
    ra, rb = scan.result, shard.result
    rep_dev = float(np.max(np.abs(ra.reputation - rb.reputation))
                    / np.max(np.abs(ra.reputation)))
    log(f"mesh {axes} over {n_dev} devices vs scan on device 0, {rounds} "
        f"rounds: reputation max dev {rep_dev:.3e} of max (bound "
        f"{MESH_REP_RTOL}); cost scan=${ra.total_cost:.6f} "
        f"shard=${rb.total_cost:.6f}; scan first round {scan.first_s:.3f}s "
        f"steady {scan.steady_s:.4f}s/round; shard first round "
        f"{shard.first_s:.3f}s steady {shard.steady_s:.4f}s/round; acc "
        f"scan={ra.final_accuracy:.4f} shard={rb.final_accuracy:.4f}")
    check(scan.engine == "jit" and shard.engine == "shard",
          f"mesh: engines {scan.engine!r}, {shard.engine!r}")
    _check_run(scan, "mesh[scan]", rounds)
    _check_run(shard, "mesh[shard]", rounds)
    for a, b in zip(scan.rounds, shard.rounds):
        check(a["digest"]["delivered_sha"] == b["digest"]["delivered_sha"],
              f"mesh: delivered masks differ in round {a['t']}")
        check(a["cost"] == b["cost"], f"mesh: $ differs in round {a['t']}")
    check(ra.total_cost == rb.total_cost
          and ra.intra_bytes == rb.intra_bytes
          and ra.cross_bytes == rb.cross_bytes, "mesh: totals differ")
    check(np.isfinite(rep_dev) and rep_dev <= MESH_REP_RTOL,
          f"mesh: reputation deviation {rep_dev:.3e} > {MESH_REP_RTOL}")
    log("mesh: masks and $ equal in every round")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh engine against the scan "
                         "engine")
    args = ap.parse_args(argv)

    cache_dir = enable_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    log(f"device: {json.dumps(device)}")
    if dev.platform != "tpu":
        print(f"no TPU found (platform {dev.platform!r}); this smoke run "
              "measures the chip only", file=sys.stderr)
        return 1
    if len(devs) != args.chips:
        print(f"--chips {args.chips} but {len(devs)} devices are visible",
              file=sys.stderr)
        return 1
    entries = (sum(1 for _ in Path(cache_dir).iterdir())
               if Path(cache_dir).is_dir() else 0)
    log(f"compile cache: {cache_dir} ({entries} entries at start)")

    from repro.federated import make_data

    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            mesh_phase()
        else:
            kernel_phase()
            base = FLConfig(attack="label_flip")
            data = make_data(base)
            round_phase(base, data)
            reference_phase(base, data)
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
