"""Compile rehearsal of each benchmark cell's round step for a described
TPU v5e chip, at the cell's own sizes (nothing runs; the TPU compiler
refuses what the chip would refuse, and reports the step's memory).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import system
from bench.spec import ROOT, load_json, resolve

WORKLOADS = load_json(ROOT / "BENCHMARK.json")["workloads"]
CELLS = [w["name"] for w in WORKLOADS if w["chips"] == 1]
MESH_CELLS = [w["name"] for w in WORKLOADS if w["chips"] == 4]
HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache off here
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _flcfg(cell):
    return system.make_flconfig(cell.config, cell.traffic)


def _data_shapes(cell, job):
    from repro.federated import engine as engine_mod
    s, r = (cell.config["data"]["samples_per_client"],
            cell.config["data"]["ref_samples"])
    n, k = job.n_clients, job.n_clouds
    return engine_mod.ClientData(
        client_x=jax.ShapeDtypeStruct((n, s) + job.input_shape, jnp.float32),
        client_y=jax.ShapeDtypeStruct((n, s), jnp.int32),
        ref_x=jax.ShapeDtypeStruct((k, r) + job.input_shape, jnp.float32),
        ref_y=jax.ShapeDtypeStruct((k, r), jnp.int32),
        malicious=jax.ShapeDtypeStruct((n,), jnp.bool_))


def _step_and_shapes(cell):
    """The scan engine's jitted round step for the cell, and the shapes of
    its (state, data) arguments, without building any data."""
    from repro.federated import engine as engine_mod
    from repro.federated.simulation import make_topology

    job = system.make_job(cell.config, cell.traffic)
    flcfg = _flcfg(cell)
    static = engine_mod.static_from(flcfg, make_topology(flcfg),
                                    cell.traffic["method"],
                                    input_shape=job.input_shape,
                                    n_classes=job.n_classes)
    eng = engine_mod.compiled(static)
    state = jax.eval_shape(lambda: eng.init_state(0))
    return eng.step, state, _data_shapes(cell, job)


@pytest.mark.parametrize("name", CELLS)
def test_cell_step_compiles_for_v5e(one_chip, name):
    cell = resolve(name)
    step, state, data = _step_and_shapes(cell)
    place = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    t = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = step.lower(jax.tree.map(place, state), jax.tree.map(place, data),
                          t).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES
    if cell.traffic["compressor"] == "topk":
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", MESH_CELLS)
def test_mesh_cell_step_compiles_for_v5e_2x2(topo, monkeypatch, name):
    """The mesh engine's step over a (4, 1) mesh of the described chips:
    it compiles, holds collectives, and fits each chip."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.federated import engine as engine_mod
    from repro.federated import sharded
    from repro.federated.simulation import make_topology

    cell = resolve(name)
    job = system.make_job(cell.config, cell.traffic)
    flcfg = _flcfg(cell)
    devices = np.asarray(topo.devices)
    monkeypatch.setattr(jax, "make_mesh", lambda shape, names: Mesh(
        devices.reshape(shape), names))
    sharded.compiled_sharded.cache_clear()
    try:
        ss = sharded.static_from_shard(flcfg, make_topology(flcfg),
                                       cell.traffic["method"],
                                       input_shape=job.input_shape,
                                       n_classes=job.n_classes,
                                       n_devices=len(devices))
        eng = sharded.compiled_sharded(ss)
        scan = engine_mod.compiled(ss.static)
        state = jax.eval_shape(lambda: scan.init_state(0))
        data = _data_shapes(cell, job)
        rep = NamedSharding(eng.mesh, P())
        split = NamedSharding(eng.mesh, P(sharded.AXES))
        state = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=rep), state)
        data = data._replace(**{
            f: jax.ShapeDtypeStruct(getattr(data, f).shape,
                                    getattr(data, f).dtype,
                                    sharding=split if f in (
                                        "client_x", "client_y", "malicious")
                                    else rep)
            for f in data._fields})
        t = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
        compiled = jax.jit(eng.step).lower(state, data, t).compile()
        mem = compiled.memory_analysis()
        assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes) < HBM_BYTES
        assert "all-reduce" in compiled.as_text()
    finally:
        sharded.compiled_sharded.cache_clear()
