"""Benchmark cells cut to a size the CPU runs in seconds: the same
configuration, traffic and limits files, with a small fleet and data."""
import dataclasses

import pytest

from bench.spec import resolve

TINY_DATA = {"n_samples": 400, "samples_per_client": 8, "ref_samples": 12,
             "dirichlet_alpha": 0.5}
TINY_FLEET = {"n_clouds": 3, "clients_per_cloud": 3, "clients_per_round": 4,
              "local_epochs": 1, "local_batch": 4}


@pytest.fixture
def tiny_cell():
    def make(name: str):
        cell = resolve(name)
        return dataclasses.replace(
            cell, config=dict(cell.config, data=TINY_DATA),
            traffic=dict(cell.traffic, **TINY_FLEET))
    return make


@pytest.fixture
def fresh_engines():
    """Compiled round engines are cached per configuration; a test that
    patches the program's training code must neither reuse nor leave a
    patched executable behind."""
    from repro.federated import engine, server
    engine._compiled.cache_clear()
    server._jitted_trainers.cache_clear()
    yield
    engine._compiled.cache_clear()
    server._jitted_trainers.cache_clear()
