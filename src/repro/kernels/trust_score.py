"""Pallas TPU kernel: fused per-client trust scoring (Eq. 7 + Eq. 11).

Input: G (N, D) per-client last-layer gradients, ref (D,) reference
gradient, rep (N,) reputations. One pass over G computes, per client,
<g_i, ḡ>, <g_i, ref>, ||g_i||² — then φ and TS on the host of the grid.

TPU mapping: grid over D-blocks (reduction dim) x N-blocks; each step
loads a (BN, BD) VMEM tile of G and the matching (BD,) slices of ref and
the precomputed column-mean ḡ, accumulating the dot products in a
(BN, 8) VMEM scratch. The final D-block writes (φ, TS, ‖g‖) as the three
columns of one (BN, 3) output block: per-row vectors ride in 2-D blocks
(rows on sublanes, full minor dim), which Mosaic tiles for any BN that
is a multiple of 8 or the whole padded N. BD is a multiple of 128 and
the inner ops are row reductions.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _kernel(g_ref_blk, gbar_blk, ref_blk, rep_blk, out_blk, acc, *,
            n_dblocks: int, eps: float):
    d_idx = pl.program_id(1)

    @pl.when(d_idx == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    g = g_ref_blk[...].astype(jnp.float32)          # (BN, BD)
    gbar = gbar_blk[...].astype(jnp.float32)        # (1, BD)
    ref = ref_blk[...].astype(jnp.float32)          # (1, BD)

    acc[:, 0] += jnp.sum(g * gbar, axis=1)          # <g_i, ḡ>
    acc[:, 1] += jnp.sum(g * ref, axis=1)           # <g_i, ref>
    acc[:, 2] += jnp.sum(g * g, axis=1)             # ||g_i||²
    acc[:, 3] += jnp.sum(gbar * gbar, axis=1)       # ||ḡ||² (bcast rows)
    acc[:, 4] += jnp.sum(ref * ref, axis=1)         # ||ref||²

    @pl.when(d_idx == n_dblocks - 1)
    def _finalize():
        dot_bar = acc[:, 0]
        dot_ref = acc[:, 1]
        norms = jnp.sqrt(jnp.maximum(acc[:, 2], 0.0))
        nbar = jnp.sqrt(jnp.maximum(acc[:, 3], 0.0))
        nref = jnp.sqrt(jnp.maximum(acc[:, 4], 0.0))
        cos_bar = dot_bar / jnp.maximum(norms * nbar, eps)
        cos_ref = dot_ref / jnp.maximum(norms * nref, eps)
        out_blk[:, 0] = jnp.maximum(cos_bar, 0.0) * norms       # phi
        out_blk[:, 1] = jnp.maximum(cos_ref, 0.0) * rep_blk[:, 0]   # ts
        out_blk[:, 2] = norms


def trust_score(grads: Array, ref: Array, reputation: Array, *,
                block_n: int = 8, block_d: int = 512,
                eps: float = 1e-12, interpret: bool
                ) -> Tuple[Array, Array, Array]:
    """Fused (φ, TS, ‖g‖) over (N, D). Pads N and D to block multiples."""
    n, d = grads.shape
    bn = min(block_n, n)
    bd = min(block_d, d)
    pn = (-n) % bn
    pd = (-d) % bd
    g = jnp.pad(grads, ((0, pn), (0, pd)))
    r = jnp.pad(ref, (0, pd))[None, :]
    rep = jnp.pad(reputation.astype(jnp.float32), (0, pn))[:, None]
    gbar = jnp.mean(g[:n].astype(jnp.float32), axis=0,
                    keepdims=True).astype(g.dtype)     # (1, D̃)
    nn, dd = g.shape
    n_dblocks = dd // bd

    out = pl.pallas_call(
        functools.partial(_kernel, n_dblocks=n_dblocks, eps=eps),
        grid=(nn // bn, n_dblocks),
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
            pl.BlockSpec((1, bd), lambda i, j: (0, j)),
            pl.BlockSpec((1, bd), lambda i, j: (0, j)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, 3), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nn, 3), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, 8), jnp.float32)],
        interpret=interpret,
    )(g, gbar, r, rep)
    return out[:n, 0], out[:n, 1], out[:n, 2]
