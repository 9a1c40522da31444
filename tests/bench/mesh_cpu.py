"""A whole run of the mesh cell at a small size on four CPU devices, for
test_harness_mesh.py (run in a process of its own, which sets the
device count before JAX starts). Prints one JSON object: ``correct`` of
the sound program and of each planted fault."""
import dataclasses
import json
import os
import sys
from pathlib import Path

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from bench import calibrate, run, system  # noqa: E402
from bench.spec import resolve  # noqa: E402

TINY_DATA = {"n_samples": 400, "samples_per_client": 8, "ref_samples": 12,
             "dirichlet_alpha": 0.5}
TINY_FLEET = {"n_clouds": 4, "clients_per_cloud": 4, "clients_per_round": 16,
              "local_epochs": 1, "local_batch": 4}


def main(name: str) -> None:
    from repro.federated import sharded
    cell = resolve(name)
    cell = dataclasses.replace(cell, config=dict(cell.config, data=TINY_DATA),
                               traffic=dict(cell.traffic, **TINY_FLEET))
    seed = 2 ** 31 + 4040
    out = {"devices": len(jax.devices())}

    def once(tag):
        sharded.compiled_sharded.cache_clear()
        res = run.run_cell(cell, seed, 0.2, False, require_chip=False)
        assert res["engine"] == "shard", res["engine"]
        out[tag] = res["correct"]
        out[tag + "_checks"] = {k: v["value"] for k, v in res["checks"].items()}

    once("sound")
    build = system.build_server

    def with_step(wrap):
        def patched(*a, **kw):
            server = build(*a, **kw)
            step = server._eng.step
            server._eng = dataclasses.replace(server._eng, step=wrap(step))
            return server
        return patched

    system.build_server = with_step(
        lambda step: lambda s, d, t: (s, step(s, d, t)[1]))
    once("unchanged_state")
    system.build_server = with_step(
        lambda step: lambda s, d, t: (lambda so: (so[0], so[1]._replace(
            delivered=so[1].delivered.at[0].set(~so[1].delivered[0]))))(
                step(s, d, t)))
    once("altered_answer")
    system.build_server = build
    from repro.federated import client
    from test_harness_faults import _half_batch_local_train
    local_train = client.local_train
    client.local_train = _half_batch_local_train
    once("half_batch")
    client.local_train = calibrate.half_batch_clients(
        local_train, TINY_DATA["samples_per_client"])
    once("half_batch_clients")
    client.local_train = local_train
    psum = sharded._psum
    sharded._psum = lambda x, axes=sharded.AXES: x
    once("no_exchange")
    sharded._psum = psum
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1])
