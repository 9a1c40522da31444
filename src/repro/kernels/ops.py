"""Jitted public wrappers for the Pallas kernels.

The platform picks the kernel's form, not the caller: each wrapper
stages its kernel through ``lax.platform_dependent``, so a program
lowered for ``tpu`` compiles the kernel with Mosaic and one lowered for
``cpu`` runs it in Pallas interpret mode (how the tests run). Lowering
for any other platform raises: there is no silent fallback.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.linear_scan import linear_scan as _linear_scan
from repro.kernels.quantize import stochastic_quantize as _stochastic_quantize
from repro.kernels.topk_mask import topk_mask as _topk_mask
from repro.kernels.trust_features import trust_features as _trust_features
from repro.kernels.trust_score import trust_score as _trust_score
from repro.kernels.weighted_agg import weighted_agg as _weighted_agg

Array = jax.Array


def _by_platform(kernel: Callable, *args, **static):
    """``kernel(*args, **static)``, interpreted on cpu, compiled on tpu."""
    return jax.lax.platform_dependent(
        *args,
        cpu=partial(kernel, **static, interpret=True),
        tpu=partial(kernel, **static, interpret=False))


@partial(jax.jit, static_argnames=("block_n", "block_d"))
def trust_score(grads: Array, ref: Array, reputation: Array, *,
                block_n: int = 8, block_d: int = 512
                ) -> Tuple[Array, Array, Array]:
    """Fused Eq. 7 + Eq. 11 statistics: (phi, ts, norms) over (N, D)."""
    return _by_platform(_trust_score, grads, ref, reputation,
                        block_n=block_n, block_d=block_d)


@partial(jax.jit, static_argnames=("block_n", "block_d"))
def trust_features(grads: Array, refs: Array, gbar: Array, med: Array,
                   w: Array, *, block_n: int = 8,
                   block_d: int = 512) -> Array:
    """Fused multi-feature trust pass: (M, D) -> (M, N_FEATURES)."""
    return _by_platform(_trust_features, grads, refs, gbar, med, w,
                        block_n=block_n, block_d=block_d)


@partial(jax.jit, static_argnames=("block_d",))
def weighted_agg(grads: Array, ts: Array, norms: Array, ref_norm: Array, *,
                 block_d: int = 512) -> Array:
    """Fused Eq. 12 + Eq. 13 aggregation: (N, D) -> (D,)."""
    return _by_platform(_weighted_agg, grads, ts, norms, ref_norm,
                        block_d=block_d)


@partial(jax.jit, static_argnames=("chunk", "block_b"))
def linear_scan(a: Array, b: Array, *, chunk: int = 32,
                block_b: int = 8) -> Array:
    """Diagonal linear recurrence h_t = a_t*h_{t-1} + b_t over axis 1."""
    return _by_platform(_linear_scan, a, b, chunk=chunk, block_b=block_b)


@partial(jax.jit, static_argnames=("k", "block_n", "block_d"))
def topk_mask(grads: Array, *, k: int, block_n: int = 8,
              block_d: int = 512) -> Array:
    """Keep the k largest-|.| entries per row of (N, D), zero the rest
    (dense decompressed form; ties at the threshold are kept)."""
    thr = jax.lax.top_k(jnp.abs(grads), k)[0][:, -1]
    return _by_platform(_topk_mask, grads, thr, block_n=block_n,
                        block_d=block_d)


@partial(jax.jit, static_argnames=("levels", "block_n", "block_d"))
def stochastic_quantize(x: Array, scale: Array, noise: Array, *, levels: int,
                        block_n: int = 8, block_d: int = 512) -> Array:
    """QSGD stochastic-rounding quantize to int32 levels in [-L, L]."""
    return _by_platform(_stochastic_quantize, x, scale, noise,
                        levels=levels, block_n=block_n, block_d=block_d)
