"""Compile rehearsals of the Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers a kernel wrapper from ``repro.kernels.ops``
for one device of a described ``v5e:2x2`` topology and compiles it with
the TPU compiler, which refuses what the chip would refuse (block shapes
off the (8, 128) tiling, more VMEM than a kernel may use). Interpret mode
on the CPU hides both. The widths are the paper's: D = 545,098 (the
CIFAR-10-shaped CNN) for the codec and aggregation kernels, the 1,290
last-layer values for the trust kernels, with m = 30 selected rows (the
paper's round) and m = 6; ``linear_scan`` at RecurrentGemma's 2,560.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a time.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops, ref

D_MODEL = 545_098        # CNN on 32x32x3, from client.cnn_init
D_LAST = 1_290           # its last layer (fc2_w + fc2_b)
D_RNN = 2_560            # RecurrentGemma-2B's RG-LRU width


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off here
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        # needs the TPU compiler (the jax[tpu] test extra), not a chip
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


def _compiled_hlo(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m", [30, 6])
def test_topk_mask_compiles(one_chip, m):
    hlo = _compiled_hlo(lambda g: ops.topk_mask(g, k=D_MODEL // 10),
                        (m, D_MODEL), sharding=one_chip)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("m", [30, 6])
def test_stochastic_quantize_compiles(one_chip, m):
    hlo = _compiled_hlo(
        lambda x, s, u: ops.stochastic_quantize(x, s, u, levels=15),
        (m, D_MODEL), (m,), (m, D_MODEL), sharding=one_chip)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("m", [30, 6])
def test_weighted_agg_compiles(one_chip, m):
    hlo = _compiled_hlo(ops.weighted_agg, (m, D_MODEL), (m,), (m,), (),
                        sharding=one_chip)
    assert "tpu_custom_call" in hlo


def test_trust_features_compiles(one_chip):
    m = 30
    hlo = _compiled_hlo(ops.trust_features, (m, D_LAST), (m, D_LAST),
                        (D_LAST,), (), (m,), sharding=one_chip)
    assert "tpu_custom_call" in hlo


def test_trust_score_compiles(one_chip):
    m = 30
    hlo = _compiled_hlo(ops.trust_score, (m, D_LAST), (D_LAST,), (m,),
                        sharding=one_chip)
    assert "tpu_custom_call" in hlo


def test_linear_scan_fits_vmem_at_rnn_width(one_chip):
    shape = (8, 256, D_RNN)
    hlo = _compiled_hlo(ops.linear_scan, shape, shape, sharding=one_chip)
    assert "tpu_custom_call" in hlo


def test_ops_interpret_on_cpu_and_refuse_unknown_platforms():
    """The platform picks the kernel's form: lowered for cpu the wrapper
    holds the interpreted kernel (no Mosaic call, oracle results);
    lowered for a platform with no kernel form it raises."""
    g = jax.random.normal(jax.random.PRNGKey(0), (3, 40))
    fn = jax.jit(lambda x: ops.topk_mask(x, k=5))
    text = fn.trace(g).lower(lowering_platforms=("cpu",)).as_text()
    assert "tpu_custom_call" not in text
    thr = jax.lax.top_k(jnp.abs(g), 5)[0][:, -1]
    np.testing.assert_array_equal(np.asarray(fn(g)),
                                  np.asarray(ref.topk_mask_ref(g, thr)))
    with pytest.raises(NotImplementedError, match="platform_index"):
        fn.trace(g).lower(lowering_platforms=("cuda",))
