"""Readings that the correctness limits of a cell are set from.

    python3 bench/calibrate.py --workload <cell> --seeds <n> [<n> ...]

For each seed, in one process on the cell's chips: the program's first
rounds through ``run_round`` (as the benchmark's set-up drives them),
then, against the float32 reference at the configuration's precision:

* ``program``  -- the program's numbers (the lower reading);
* ``control``  -- the reference computed in bfloat16 throughout (the
  precision a later change might be tempted by; the upper reading);
* the planted faults of ``bench.reference.FAULTS``, each in the
  reference put in the program's place: ``half_batch`` (every SGD step
  of clients and references over half its minibatch),
  ``half_batch_clients`` (the clients' alone), and where uplinks are
  compressed ``no_ef_client`` (the client error-feedback residual never
  added back);
* ``program_half_batch_clients`` -- the same client fault planted in the
  program's own LocalTrain;
* ``unchanged_state`` -- a step that returns its state unchanged;
* ``no_exchange`` -- on a cell of several chips, the mesh engine with its
  cross-chip sums left out.

One JSON line per seed and reading goes to stdout. Not run by the
benchmark itself; ``tests/bench/test_harness_control.py`` runs it at a
small size.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
from pathlib import Path
from typing import Dict, Iterator, List, Sequence

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _patched_rounds(cell, data, seed: int, rounds: int, module, name,
                   value):
    """The program's first rounds with ``module.name`` replaced by
    ``value`` (compiled afresh, and the compiled engines cleared after)."""
    from repro.federated import engine, server, sharded

    from bench import system

    def clear():
        engine._compiled.cache_clear()
        server._jitted_trainers.cache_clear()
        sharded.compiled_sharded.cache_clear()
    saved = getattr(module, name)
    clear()
    setattr(module, name, value)
    try:
        srv = system.build_server(cell.config, cell.traffic, data, seed)
        return system.first_rounds(srv, rounds)
    finally:
        setattr(module, name, saved)
        clear()


def program_without_exchange(cell, data, seed: int, rounds: int):
    """The mesh engine's first rounds with its cross-chip sums left out
    (each chip reduces its own clients only): a planted fault."""
    from repro.federated import sharded
    return _patched_rounds(cell, data, seed, rounds, sharded, "_psum",
                           lambda x, axes=sharded.AXES: x)


def half_batch_clients(train, n_client: int):
    """``train`` (the program's ``local_train``) with each step taken over
    the first half of its minibatch where it trains a client, told apart
    from a reference training by its ``n_client`` sample rows."""
    import jax

    from repro.federated import client

    def half(params, x, y, key, *, epochs, batch, lr):
        if x.shape[0] != n_client:
            return train(params, x, y, key, epochs=epochs, batch=batch, lr=lr)
        steps = epochs * max(1, n_client // batch)

        def step(p, k):
            ix = jax.random.randint(k, (batch,), 0, n_client)[:batch // 2]
            g = jax.grad(client.xent_loss)(p, x[ix], y[ix])
            return jax.tree.map(lambda w, gw: w - lr * gw, p, g), None
        local, _ = jax.lax.scan(step, params, jax.random.split(key, steps))
        return jax.tree.map(lambda a, b: a - b, params, local)
    return half


def program_half_batch_clients(cell, data, seed: int, rounds: int):
    """The program's first rounds with the client fault of
    ``half_batch_clients``; the reference trainings stay as they are."""
    from repro.federated import client
    data_cfg = cell.config["data"]
    if data_cfg["samples_per_client"] == data_cfg["ref_samples"]:
        raise ValueError("clients and references hold as many rows: the "
                         "client fault cannot tell them apart")
    return _patched_rounds(cell, data, seed, rounds, client, "local_train",
                           half_batch_clients(client.local_train,
                                              data_cfg["samples_per_client"]))


def program_rounds(cell, seed: int, rounds: int = 3):
    """(job, data, program seed, the program's first rounds)."""
    from bench import system
    pseed = system.program_seed(seed)
    job = system.make_job(cell.config, cell.traffic)
    data = system.make_data(cell.config, job, pseed)
    server = system.build_server(cell.config, cell.traffic, data, pseed)
    prog = system.first_rounds(server, rounds)
    del server
    gc.collect()
    return job, data, pseed, prog


def readings(cell, seed: int, rounds: int = 3,
             precisions: Sequence[str] = ("",), faults: bool = True
             ) -> Iterator[Dict]:
    """The rows set out above for one seed, against the reference at each
    of ``precisions`` (a name of ``reference.PRECISIONS``; "" is the
    configuration's ``matmul_precision``); with ``faults`` false, the
    program's row alone."""
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from bench import compare, reference

    job, data, pseed, prog = program_rounds(cell, seed, rounds)
    sides = {"program": prog}
    if faults:
        sides["program_half_batch_clients"] = program_half_batch_clients(
            cell, data, pseed, rounds)
    if faults and cell.chips > 1:
        sides["no_exchange"] = program_without_exchange(cell, data, pseed,
                                                        rounds)
    mal = reference.draw_malicious(job, pseed)
    y = reference.poison_labels(job, data.client_y, mal, pseed)
    args = (pseed, data.client_x, y, data.ref_x, data.ref_y, mal, rounds)
    compressed = job.codec("intra") != "none"
    if faults:
        sides["control"] = reference.run_rounds(
            job, *args, dtype=jnp.bfloat16, precision=lax.Precision.DEFAULT)
    for name in precisions:
        name = name or cell.config["matmul_precision"]
        prec = reference.PRECISIONS[name]
        ref = reference.run_rounds(job, *args, precision=prec)
        for fault in reference.FAULTS:
            if not faults or (fault == "no_ef_client" and not compressed):
                continue
            sides[fault] = reference.run_rounds(
                dataclasses.replace(job, fault=fault), *args, precision=prec)
        # a step that returns its state unchanged: the first round's masks
        # and bytes stand, nothing else moves
        n = job.n_clients
        if faults:
            sides["unchanged_state"] = [ref[0]] + [
                dict(r, params=ref[0]["params"],
                     rep=np.full(n, 1.0 / n, np.float32),
                     res_client_norm=0.0)
                for r in ref[1:]]
        for side_name, side in sides.items():
            yield {"seed": seed, "reading": side_name, "precision": name,
                   **compare.numbers(side, ref, compressed),
                   **compare.later_rounds(side, ref),
                   "update_leaves": compare.leaf_gaps(side, ref, 1),
                   "change_leaves": compare.leaf_gaps(side, ref, rounds),
                   "update_cos_leaves": compare.leaf_cos(
                       side[0]["params"], side[1]["params"],
                       ref[0]["params"], ref[1]["params"])}


def main(argv: List[str] | None = None) -> int:
    from bench.run import setup_jax
    from bench.spec import resolve
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precisions", nargs="+", default=[""],
                    help="reference matmul precisions (default: the "
                         "configuration's)")
    ap.add_argument("--program-only", action="store_true",
                    help="the program's readings alone, no faults")
    args = ap.parse_args(argv)
    cell = resolve(args.workload)
    setup_jax()
    for seed in args.seeds:
        for row in readings(cell, seed, precisions=args.precisions,
                            faults=not args.program_only):
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
