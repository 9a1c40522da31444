"""Pallas TPU kernel: fused trust-weighted aggregation (Eq. 12 + Eq. 13).

out[d] = Σ_i TS_i · (‖g_ref‖ / ‖g_i‖) · G[i, d]  /  Σ_i TS_i

Grid tiles the D axis; each step loads an (N, BD) VMEM tile of G plus the
(N,) weight vector (computed once on host-of-grid from TS/norms — cheap),
and emits the (BD,) weighted column sum as a single (1, N) x (N, BD)
MXU matmul. N (clients) is small (<=256), so a full N-column strip fits
VMEM at BD=512: 256 x 512 x 4B = 512 KiB."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _kernel(g_blk, w_blk, out_blk):
    g = g_blk[...].astype(jnp.float32)          # (N, BD)
    w = w_blk[...].astype(jnp.float32)          # (1, N)
    out_blk[...] = (w @ g)                      # (1, BD)


def weighted_agg(grads: Array, ts: Array, norms: Array, ref_norm: Array,
                 *, block_d: int = 512, eps: float = 1e-12,
                 interpret: bool) -> Array:
    """(N, D) x weights -> (D,) aggregate. See ref.weighted_agg_ref."""
    n, d = grads.shape
    bd = min(block_d, d)
    pd = (-d) % bd
    g = jnp.pad(grads, ((0, 0), (0, pd)))
    w = (ts.astype(jnp.float32)
         * (ref_norm / jnp.maximum(norms.astype(jnp.float32), eps))
         / jnp.maximum(jnp.sum(ts.astype(jnp.float32)), eps))[None, :]
    dd = g.shape[1]

    out = pl.pallas_call(
        _kernel,
        grid=(dd // bd,),
        in_specs=[
            pl.BlockSpec((n, bd), lambda j: (0, j)),
            pl.BlockSpec((1, n), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bd), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, dd), jnp.float32),
        interpret=interpret,
    )(g, w)
    return out[0, :d]
