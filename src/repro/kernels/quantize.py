"""Pallas TPU kernel: QSGD-style stochastic-rounding quantization.

Input: X (N, D) per-client updates, scale (N, 1) per-row max-|x| scales
and U (N, D) uniform [0, 1) noise; static ``levels`` L. Output int32
levels q in [-L, L] with

    q[i, d] = sign(x) * floor(|x| / scale_i * L + u)

so that E_u[q * scale / L] = x — the unbiasedness the trust statistics
rely on (they are computed on dequantized updates downstream).

The randomness is an explicit input rather than ``pltpu.prng_random_bits``
so the kernel draws the same noise in interpret mode on the CPU and
compiled on the TPU, and is checkable against
``ref.stochastic_quantize_ref``; on the chip the noise tile streams from
HBM alongside X.

TPU mapping: grid over N-blocks x D-blocks, all element-wise VPU work on
(BN, BD) VMEM tiles; the (BN, 1) scale column rides along each row block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _kernel(x_blk, s_blk, u_blk, q_blk, *, levels: int, eps: float):
    x = x_blk[...].astype(jnp.float32)              # (BN, BD)
    s = jnp.maximum(s_blk[...].astype(jnp.float32), eps)   # (BN, 1)
    v = x / s * levels                              # |v| <= L by construction
    xi = jnp.floor(jnp.abs(v) + u_blk[...].astype(jnp.float32))
    xi = jnp.minimum(xi, float(levels))
    q_blk[...] = (jnp.sign(v) * xi).astype(jnp.int32)


def stochastic_quantize(x: Array, scale: Array, noise: Array, *,
                        levels: int, block_n: int = 8, block_d: int = 512,
                        eps: float = 1e-12, interpret: bool) -> Array:
    """Quantize (N, D) to int32 levels in [-levels, levels].

    ``scale``: (N,) per-row scales (max |x| for the QSGD linf variant).
    ``noise``: (N, D) uniform [0, 1) — supplies the stochastic rounding.
    """
    n, d = x.shape
    bn = min(block_n, n)
    bd = min(block_d, d)
    pn = (-n) % bn
    pd = (-d) % bd
    xp = jnp.pad(x, ((0, pn), (0, pd)))
    up = jnp.pad(noise, ((0, pn), (0, pd)))
    sp = jnp.pad(scale.reshape(-1, 1), ((0, pn), (0, 0)))
    nn, dd = xp.shape

    q = pl.pallas_call(
        functools.partial(_kernel, levels=levels, eps=eps),
        grid=(nn // bn, dd // bd),
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nn, dd), jnp.int32),
        interpret=interpret,
    )(xp, sp, up)
    return q[:n, :d]
