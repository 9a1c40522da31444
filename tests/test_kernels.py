"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (hypothesis) +
directed cases. On the CPU the ops wrappers run the kernels in interpret
mode; tests/test_tpu_compile.py compiles them for the chip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_fallback import given, settings, st

from repro.kernels import ops, ref

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


@given(n=st.integers(2, 17), d=st.integers(3, 300),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
       seed=st.integers(0, 5))
def test_trust_score_matches_ref(n, d, dtype, seed):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    g = _rand(k1, (n, d), dtype)
    r = _rand(k2, (d,), dtype)
    rep = jax.random.uniform(k3, (n,))
    phi, ts, norms = ops.trust_score(g, r, rep, block_n=4, block_d=128)
    phi_r, ts_r, norms_r = ref.trust_score_ref(g, r, rep)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(phi, phi_r, rtol=tol, atol=tol)
    np.testing.assert_allclose(ts, ts_r, rtol=tol, atol=tol)
    np.testing.assert_allclose(norms, norms_r, rtol=tol, atol=tol)


@given(n=st.integers(2, 12), d=st.integers(2, 260),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
       seed=st.integers(0, 5))
def test_weighted_agg_matches_ref(n, d, dtype, seed):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    g = _rand(k1, (n, d), dtype)
    ts = jax.random.uniform(k2, (n,)) + 0.1
    norms = jnp.linalg.norm(g.astype(jnp.float32), axis=1)
    ref_norm = jnp.asarray(1.7)
    out = ops.weighted_agg(g, ts, norms, ref_norm, block_d=64)
    out_r = ref.weighted_agg_ref(g, ts, norms, ref_norm)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(out, out_r, rtol=tol, atol=tol)


@given(b=st.integers(1, 5), t=st.integers(1, 70), d=st.integers(1, 40),
       seed=st.integers(0, 5))
def test_linear_scan_matches_ref(b, t, d, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.uniform(k1, (b, t, d), minval=0.1, maxval=0.99)
    x = jax.random.normal(k2, (b, t, d))
    out = ops.linear_scan(a, x, chunk=16, block_b=2)
    out_r = ref.linear_scan_ref(a, x)
    np.testing.assert_allclose(out, out_r, rtol=2e-5, atol=2e-5)


def test_linear_scan_is_true_recurrence():
    """Directed: compare against an explicit python loop."""
    rng = np.random.default_rng(0)
    a = rng.uniform(0.5, 0.95, (2, 9, 3)).astype(np.float32)
    b = rng.normal(size=(2, 9, 3)).astype(np.float32)
    h = np.zeros((2, 3), np.float32)
    expect = np.zeros_like(b)
    for t in range(9):
        h = a[:, t] * h + b[:, t]
        expect[:, t] = h
    out = ops.linear_scan(jnp.asarray(a), jnp.asarray(b), chunk=4)
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-6)


# shapes drawn from a small pool so interpret-mode retraces are bounded
@given(n=st.sampled_from([1, 3, 8]), d=st.sampled_from([4, 129, 300]),
       seed=st.integers(0, 5))
def test_topk_mask_matches_ref(n, d, seed):
    g = jax.random.normal(jax.random.PRNGKey(seed), (n, d))
    k = max(1, d // 7)
    out = ops.topk_mask(g, k=k, block_n=4, block_d=128)
    thr = jax.lax.top_k(jnp.abs(g), k)[0][:, -1]
    out_r = ref.topk_mask_ref(g, thr)
    np.testing.assert_allclose(out, out_r, rtol=1e-5, atol=1e-5)
    # exactly k survivors per row (ties have measure zero for normals)
    assert int((np.array(out) != 0).sum(axis=1).max()) == min(k, d)


@given(n=st.sampled_from([1, 5]), d=st.sampled_from([6, 200]),
       levels=st.sampled_from([1, 15, 127]), seed=st.integers(0, 5))
def test_stochastic_quantize_matches_ref(n, d, levels, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (n, d))
    u = jax.random.uniform(k2, (n, d))
    scale = jnp.max(jnp.abs(x), axis=1)
    q = ops.stochastic_quantize(x, scale, u, levels=levels, block_n=4,
                                block_d=128)
    q_r = ref.stochastic_quantize_ref(x, scale, u, levels)
    np.testing.assert_allclose(np.array(q), np.array(q_r), atol=1e-5)
    assert int(jnp.abs(q).max()) <= levels
    # dequantized error is bounded by one quantization step
    err = jnp.abs(ref.dequantize_ref(q, scale, levels) - x)
    assert float(err.max()) <= float(scale.max()) / levels + 1e-5


def test_trust_score_agrees_with_core_shapley():
    """The kernel's phi equals repro.core.shapley.gradient_contribution."""
    from repro.core import gradient_contribution
    key = jax.random.PRNGKey(3)
    g = jax.random.normal(key, (8, 96))
    phi_k, _, _ = ops.trust_score(g, jnp.ones(96), jnp.ones(8) / 8)
    phi_c = gradient_contribution(g)
    np.testing.assert_allclose(phi_k, phi_c, rtol=1e-5, atol=1e-5)


def test_rglru_kernel_path_matches_xla_path():
    """rglru_forward(use_kernel=True) == associative-scan reference."""
    from dataclasses import replace
    from repro.configs import get_arch, reduced
    from repro.models.rglru import init_rglru, rglru_forward
    cfg = reduced(get_arch("recurrentgemma-2b"), d_model=64, layers=1)
    params = init_rglru(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 64))
    y_xla = rglru_forward(params, x, cfg, use_kernel=False)
    y_pl = rglru_forward(params, x, cfg, use_kernel=True)
    np.testing.assert_allclose(np.array(y_xla), np.array(y_pl),
                               rtol=2e-4, atol=2e-5)
