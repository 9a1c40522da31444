"""Pallas TPU kernel: chunked diagonal linear recurrence
h_t = a_t ⊙ h_{t-1} + b_t (the RG-LRU state update, DESIGN.md §6).

TPU mapping: grid = (B-blocks, T-chunks) with the time axis iterated
sequentially (TPU grids execute in order, last axis fastest), carrying the
(BB, D) running state in a VMEM scratch across chunk steps. Within a
chunk the recurrence runs as an unrolled loop over the chunk's rows —
each row is a (BB, D) VPU multiply-add, so the sequential depth is
chunk-length while all batch/feature lanes stay saturated.

VMEM: each grid step keeps about eight (BB, C, D) f32 tiles live (a, b
and h double-buffered, plus the stacked chunk), so the time chunk and
then the batch block shrink until one tile fits ``_TILE_BYTES``; at
RecurrentGemma's D = 2,560 that is C = 8 with BB = 8. Chunking changes
no result: every row is the same multiply-add in the same order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

_TILE_BYTES = 1 << 20   # one (BB, C, D) f32 tile; v5e scopes 16 MiB of VMEM


def _kernel(a_blk, b_blk, h_out, carry, *, chunk: int):
    t_idx = pl.program_id(1)

    @pl.when(t_idx == 0)
    def _init():
        carry[...] = jnp.zeros_like(carry)

    a = a_blk[...].astype(jnp.float32)           # (BB, C, D)
    b = b_blk[...].astype(jnp.float32)
    h = carry[...]                               # (BB, D)
    rows = []
    for t in range(chunk):
        h = a[:, t] * h + b[:, t]
        rows.append(h)
    out = jnp.stack(rows, axis=1)                # (BB, C, D)
    carry[...] = h
    h_out[...] = out.astype(h_out.dtype)


def linear_scan(a: Array, b: Array, *, chunk: int = 32,
                block_b: int = 8, interpret: bool) -> Array:
    """h_t = a_t*h_{t-1} + b_t over axis 1. a, b: (B, T, D) -> (B, T, D)."""
    bsz, t, d = a.shape
    bb = min(block_b, bsz)
    c = min(chunk, t)
    # C stays a multiple of 8 (or the whole T): the block's sublane dim
    while c > 8 and bb * c * d * 4 > _TILE_BYTES:
        c = max(8, c // 16 * 8)
    while bb > 1 and bb * c * d * 4 > _TILE_BYTES:
        bb //= 2
    pb = (-bsz) % bb
    pt = (-t) % c
    ap = jnp.pad(a, ((0, pb), (0, pt), (0, 0)))
    bp = jnp.pad(b, ((0, pb), (0, pt), (0, 0)))
    bt, tt = ap.shape[0], ap.shape[1]

    out = pl.pallas_call(
        functools.partial(_kernel, chunk=c),
        grid=(bt // bb, tt // c),
        in_specs=[
            pl.BlockSpec((bb, c, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((bb, c, d), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((bb, c, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bt, tt, d), a.dtype),
        scratch_shapes=[pltpu.VMEM((bb, d), jnp.float32)],
        interpret=interpret,
    )(ap, bp)
    return out[:bsz, :t]
