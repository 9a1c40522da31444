"""Pallas TPU kernel: fused top-k threshold + mask for gradient sparsification.

Input: G (N, D) per-client updates and thr (N, 1) per-row magnitude
thresholds (the k-th largest |g| of each row, computed once on the host
of the grid with ``lax.top_k``). Output: G with every entry whose
magnitude falls below its row threshold zeroed — the dense "decompressed"
form of a top-k sparsified update.

TPU mapping: grid over N-blocks x D-blocks; each step loads a (BN, BD)
VMEM tile of G plus the matching (BN, 1) threshold slice and applies the
compare+select on the VPU. Purely element-wise, so BD=512 (4 lanes of
128) keeps the tile VMEM-resident at any client count.

Tie semantics: |g| == thr entries are KEPT, so rows with ties may retain
more than k entries. Byte accounting in ``repro.compress`` uses the
analytic k, which is exact for continuous-valued gradients (ties have
measure zero).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _kernel(g_blk, thr_blk, out_blk):
    g = g_blk[...]                                  # (BN, BD)
    thr = thr_blk[...]                              # (BN, 1) broadcast
    out_blk[...] = jnp.where(jnp.abs(g) >= thr, g, jnp.zeros_like(g))


def topk_mask(grads: Array, thr: Array, *, block_n: int = 8,
              block_d: int = 512, interpret: bool) -> Array:
    """Zero every |G[i, d]| < thr[i]. See ref.topk_mask_ref."""
    n, d = grads.shape
    bn = min(block_n, n)
    bd = min(block_d, d)
    pn = (-n) % bn
    pd = (-d) % bd
    g = jnp.pad(grads, ((0, pn), (0, pd)))
    # padded rows threshold at +inf so the pad region stays exactly zero
    t = jnp.pad(thr.reshape(-1, 1).astype(grads.dtype), ((0, pn), (0, 0)),
                constant_values=jnp.inf)
    nn, dd = g.shape

    out = pl.pallas_call(
        _kernel,
        grid=(nn // bn, dd // bd),
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nn, dd), grads.dtype),
        interpret=interpret,
    )(g, t)
    return out[:n, :d]
