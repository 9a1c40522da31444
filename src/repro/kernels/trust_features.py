"""Pallas TPU kernel: fused per-client trust FEATURE pass.

One pass over the delivered (M, D) last-layer matrix emitting the four
multi-feature trust signals of ``repro.core.features`` per client:
norm profile vs the selected-median norm, ReLU cosine to the client's
own-cloud reference row, elementwise sign agreement with the selected
aggregate, and the saturating norm-clipped loss-delta proxy.

TPU mapping mirrors ``trust_score.py``: grid over N-blocks × D-blocks
(reduction dim); each step loads a (BN, BD) tile of G and the matching
tile of the per-row reference matrix plus the broadcast (BD,) aggregate
slice, accumulating per-row <g, ref>, ‖g‖², ‖ref‖² and the
sign-agreement count in a (BN, 8) VMEM scratch. The final D-block folds
in the (pre-reduced) median norm and the (BN, 1) delivery-weight column
and writes the four features as the columns of one (BN, N_FEATURES)
output block (per-row vectors ride in 2-D blocks, which Mosaic tiles for
any BN that is a multiple of 8 or the whole padded M). Zero-padding of
both axes is safe by construction: padded coordinates contribute 0 to
every dot product and never count as sign agreement, and padded rows
carry w = 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.features import N_FEATURES

Array = jax.Array


def _kernel(g_blk, ref_blk, gbar_blk, med_blk, w_blk, out_blk, acc,
            *, n_dblocks: int, d_true: int, eps: float):
    d_idx = pl.program_id(1)

    @pl.when(d_idx == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    g = g_blk[...].astype(jnp.float32)              # (BN, BD)
    r = ref_blk[...].astype(jnp.float32)            # (BN, BD)
    gbar = gbar_blk[...].astype(jnp.float32)        # (1, BD)

    acc[:, 0] += jnp.sum(g * r, axis=1)             # <g_i, ref_i>
    acc[:, 1] += jnp.sum(g * g, axis=1)             # ||g_i||²
    acc[:, 2] += jnp.sum(r * r, axis=1)             # ||ref_i||²
    acc[:, 3] += jnp.sum((g * gbar > 0).astype(jnp.float32), axis=1)

    @pl.when(d_idx == n_dblocks - 1)
    def _finalize():
        dot_ref = acc[:, 0]
        norm_g = jnp.sqrt(jnp.maximum(acc[:, 1], 0.0))
        norm_r = jnp.sqrt(jnp.maximum(acc[:, 2], 0.0))
        agree = acc[:, 3]

        med_raw = med_blk[0, 0]
        med = jnp.where(jnp.isnan(med_raw) | ~(med_raw > 0), 1.0, med_raw)
        w = w_blk[:, 0]

        f0 = 1.0 / (1.0 + jnp.abs(jnp.log(jnp.maximum(norm_g, eps) / med)))
        f1 = jnp.maximum(dot_ref / jnp.maximum(norm_g * norm_r, eps), 0.0)
        f2 = agree / float(d_true)
        ratio = jnp.maximum(norm_g, eps) / med
        x = f1 * jnp.minimum(ratio, 1.0 / ratio)
        f3 = x / (1.0 + x)

        out_blk[:, 0] = f0 * w
        out_blk[:, 1] = f1 * w
        out_blk[:, 2] = f2 * w
        out_blk[:, 3] = f3 * w


def trust_features(grads: Array, refs: Array, gbar: Array, med: Array,
                   w: Array, *, block_n: int = 8, block_d: int = 512,
                   eps: float = 1e-12, interpret: bool) -> Array:
    """Fused (M, N_FEATURES) feature pass over (M, D). Pads M and D to
    block multiples; ``med`` is the (possibly NaN) selected-median norm
    and is sanitized in-kernel exactly like the jnp oracle."""
    m, d = grads.shape
    bn = min(block_n, m)
    bd = min(block_d, d)
    pm = (-m) % bn
    pd = (-d) % bd
    g = jnp.pad(grads, ((0, pm), (0, pd)))
    r = jnp.pad(refs, ((0, pm), (0, pd)))
    gb = jnp.pad(gbar, (0, pd))[None, :]
    wp = jnp.pad(w.astype(jnp.float32), (0, pm))[:, None]
    med_arr = jnp.asarray(med, jnp.float32).reshape(1, 1)
    mm, dd = g.shape
    n_dblocks = dd // bd

    out = pl.pallas_call(
        functools.partial(_kernel, n_dblocks=n_dblocks, d_true=d, eps=eps),
        grid=(mm // bn, n_dblocks),
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
            pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
            pl.BlockSpec((1, bd), lambda i, j: (0, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, N_FEATURES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mm, N_FEATURES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, 8), jnp.float32)],
        interpret=interpret,
    )(g, r, gb, med_arr, wp)
    return out[:m]
