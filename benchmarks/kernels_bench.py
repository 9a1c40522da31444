"""Kernel micro-benchmarks: each Pallas kernel (compiled on a TPU,
interpreted on the CPU) checked against ref.py, plus the XLA reference
path's time on the device it runs on. The Pallas rows carry no time: their
derived column holds the max error and the analytic VMEM working set of
the chosen BlockSpec tiling."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from benchmarks.common import emit, time_fn


def run() -> None:
    key = jax.random.PRNGKey(0)
    # trust_score on a realistic last-layer matrix: 32 clients x 0.5M
    n, d = 32, 1 << 19
    g = jax.random.normal(key, (n, d), jnp.float32)
    r = jax.random.normal(key, (d,), jnp.float32)
    rep = jnp.full((n,), 1.0 / n)

    ref_fn = jax.jit(ref.trust_score_ref)
    us = time_fn(lambda: jax.block_until_ready(ref_fn(g, r, rep)), iters=3)
    emit("kernel/trust_score/xla_ref", us, f"N={n};D={d}")
    phi_k, ts_k, _ = ops.trust_score(g, r, rep, block_n=8, block_d=512)
    phi_r, ts_r, _ = ref_fn(g, r, rep)
    err = float(jnp.max(jnp.abs(phi_k - phi_r)))
    vmem_kb = (8 * 512 + 2 * 512 + 8 * 8) * 4 / 1024
    emit("kernel/trust_score/pallas", 0.0,
         f"max_err={err:.2e};vmem_tile_kb={vmem_kb:.0f}")

    agg_ref = jax.jit(ref.weighted_agg_ref)
    norms = jnp.linalg.norm(g, axis=1)
    us = time_fn(lambda: jax.block_until_ready(
        agg_ref(g, rep, norms, jnp.asarray(1.0))), iters=3)
    emit("kernel/weighted_agg/xla_ref", us, f"N={n};D={d}")
    out_k = ops.weighted_agg(g, rep, norms, jnp.asarray(1.0), block_d=512)
    out_r = agg_ref(g, rep, norms, jnp.asarray(1.0))
    emit("kernel/weighted_agg/pallas", 0.0,
         f"max_err={float(jnp.max(jnp.abs(out_k - out_r))):.2e};"
         f"vmem_tile_kb={(n * 512 + n + 512) * 4 / 1024:.0f}")

    # linear_scan: RG-LRU shape (B=8, T=2048, D=256)
    a = jax.random.uniform(key, (8, 2048, 256), minval=0.5, maxval=0.99)
    b = jax.random.normal(key, (8, 2048, 256))
    scan_ref = jax.jit(ref.linear_scan_ref)
    us = time_fn(lambda: jax.block_until_ready(scan_ref(a, b)), iters=3)
    emit("kernel/linear_scan/xla_assoc_scan", us, "B=8;T=2048;D=256")
    out_k = ops.linear_scan(a[:, :128], b[:, :128], chunk=32)
    out_r = scan_ref(a[:, :128], b[:, :128])
    emit("kernel/linear_scan/pallas", 0.0,
         f"max_err={float(jnp.max(jnp.abs(out_k - out_r))):.2e};"
         f"vmem_tile_kb={(8 * 32 * 256 * 3 + 8 * 256) * 4 / 1024:.0f}")


if __name__ == "__main__":
    run()
