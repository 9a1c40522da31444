"""Compile rehearsals of the Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers a kernel wrapper from ``repro.kernels.ops``
for one device of a described ``v5e:2x2`` topology and compiles it with
the TPU compiler, which refuses what the chip would refuse (block shapes
off the (8, 128) tiling, more VMEM than a kernel may use). Interpret mode
on the CPU hides both. The widths are the paper's: D = 545,098 (the
CIFAR-10-shaped CNN) for the codec and aggregation kernels, the 1,290
last-layer values for the trust kernels, with m = 30 selected rows (the
paper's round) and m = 6; ``linear_scan`` at RecurrentGemma's 2,560.
The scan engine's whole round step is compiled too, at a small fleet, to
see which form of the CNN's convolution the train loop holds.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a time.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import FLConfig
from repro.federated import FLServer, make_data, make_topology
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


@pytest.fixture(scope="module")
def described_chip(request):
    """``one_chip``, or a skip where no v5e topology can be described (the
    TPU compiler is missing, or another process holds it)."""
    try:
        return request.getfixturevalue("one_chip")
    except RuntimeError as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


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


_TRAIN_OP = re.compile(r"= \w+\[([\d,]*)\]\S* (\w[\w-]*)\((.*)"
                       r'op_name="jit\(round_step\)/round\.train/')


def _train_ops(hlo):
    """(opcode, shape, operands and attributes) of every op under the
    round's ``round.train`` scope."""
    return [(m.group(2), tuple(int(d) for d in m.group(1).split(",") if d),
             m.group(3))
            for m in map(_TRAIN_OP.search, hlo.splitlines()) if m]


def test_scan_step_convolves_natively_on_tpu_and_by_im2col_on_cpu(
        described_chip):
    """Lowered for the TPU, the clients' train loop convolves the 32x32x3
    images with 3x3 windows and builds no 27- or 288-wide im2col patches;
    lowered for the CPU it builds those patches and has no such window."""
    fl = FLConfig(n_clouds=2, clients_per_cloud=2, clients_per_round=4,
                  local_epochs=1, local_batch=4, ref_samples=8)
    data = make_data(fl, "cifar10", seed=0, n_samples=200,
                     samples_per_client=8)
    srv = FLServer(fl, make_topology(fl), data, method="cost_trustfl",
                   seed=0, engine="jit")
    shape = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=described_chip)
    on_tpu = srv._eng.step.lower(
        jax.tree.map(shape, srv._eng_state),
        jax.tree.map(shape, srv._eng_data),
        jax.ShapeDtypeStruct((), jnp.int32, weak_type=True,
                             sharding=described_chip)).compile().as_text()
    on_cpu = srv._eng.step.lower(srv._eng_state, srv._eng_data,
                                 0).compile().as_text()
    patches = {9 * 3, 9 * 32}          # 3x3 taps of the image, of conv1
    for hlo, native in ((on_tpu, True), (on_cpu, False)):
        ops_ = _train_ops(hlo)
        windows = [s for op, s, rest in ops_
                   if op == "convolution" and "window={size=3x3" in rest]
        widths = {s[-1] for op, s, _ in ops_ if op == "concatenate" and s}
        if native:       # conv1 over the image: 32x32 out, 32 channels
            assert (32, 32, 32) in {s[-3:] for s in windows}, windows
            assert not widths & patches, widths
        else:
            assert ops_ and not windows, windows
            assert patches <= widths, widths
