"""A whole benchmark run (``bench.run.run_cell``, past its look for a
chip) at a small size on the CPU, with the timed path broken underneath:
``correct`` has to come out false for every fault a one-chip cell can
have, and true for the program as it is."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench import calibrate, run, system
from bench.spec import ROOT, load_json, resolve

CELLS = [w["name"] for w in load_json(ROOT / "BENCHMARK.json")["workloads"]
         if w["chips"] == 1]
COMPRESSED = [n for n in CELLS if resolve(n).traffic["compressor"] != "none"]
SEED = 2 ** 31 + 2024


def _patch_step(monkeypatch, wrap):
    """Wrap the compiled round step of every server the run builds."""
    build = system.build_server

    def patched(*args, **kw):
        server = build(*args, **kw)
        server._eng = dataclasses.replace(server._eng,
                                          step=wrap(server._eng.step))
        return server
    monkeypatch.setattr(system, "build_server", patched)


def _half_batch_local_train(params, x, y, key, *, epochs, batch, lr):
    """The program's LocalTrain with each step's gradient taken over the
    first half of its minibatch."""
    from repro.federated.client import xent_loss
    n = x.shape[0]
    total = epochs * max(1, n // batch)

    def step(p, k):
        ix = jax.random.randint(k, (batch,), 0, n)[:batch // 2]
        g = jax.grad(xent_loss)(p, x[ix], y[ix])
        return jax.tree.map(lambda w, gw: w - lr * gw, p, g), None

    local, _ = jax.lax.scan(step, params, jax.random.split(key, total))
    return jax.tree.map(lambda a, b: a - b, params, local)


def _unchanged_state(step):
    return lambda state, data, t: (state, step(state, data, t)[1])


def _altered_answer(step):
    def broken(state, data, t):
        state, out = step(state, data, t)
        return state, out._replace(delivered=out.delivered.at[0].set(
            ~out.delivered[0]))
    return broken


@pytest.mark.parametrize("name", CELLS)
def test_sound_program_is_correct(tiny_cell, name):
    result = run.run_cell(tiny_cell(name), SEED, 0.5, False, require_chip=False)
    assert result["correct"], result["checks"]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "half_batch_clients", "altered_answer"])
def test_fault_is_not_correct(tiny_cell, fresh_engines, monkeypatch, name,
                              fault):
    if fault == "unchanged_state":
        _patch_step(monkeypatch, _unchanged_state)
    elif fault == "altered_answer":
        _patch_step(monkeypatch, _altered_answer)
    elif fault == "half_batch_clients":
        from repro.federated import client
        n_client = tiny_cell(name).config["data"]["samples_per_client"]
        monkeypatch.setattr(client, "local_train", calibrate.half_batch_clients(
            client.local_train, n_client))
    else:
        from repro.federated import client
        monkeypatch.setattr(client, "local_train", _half_batch_local_train)
    result = run.run_cell(tiny_cell(name), SEED, 0.5, False, require_chip=False)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("name", COMPRESSED)
def test_residual_never_added_back_is_not_correct(tiny_cell, fresh_engines,
                                                  monkeypatch, name):
    """Client error feedback that drops what the last round left in the
    residual table: the second round reads it."""
    from repro.federated import engine
    ef_step = engine.ef_step_masked

    def ef_without_residual(codec, x, res, row_mask, *a):
        sent, new = ef_step(codec, x, jnp.zeros_like(res), row_mask, *a)
        return sent, jnp.where(row_mask[:, None], new, res)
    monkeypatch.setattr(engine, "ef_step_masked", ef_without_residual)
    result = run.run_cell(tiny_cell(name), SEED, 0.5, False, require_chip=False)
    assert not result["correct"], result["checks"]
