"""The system under test, as the benchmark drives it: ``FLServer`` with
``engine="auto"``, fed the benchmark's own data, stepped by
``run_round(t)``; and the job description both it and the reference
are built from (a configuration file plus a traffic mix)."""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.datagen import FleetData, make_fleet_data
from bench.reference import Job, job_for

# the reference LocalTrain batch: the clients' default batch (paper §V-A)
REF_BATCH = 32
# traffic keys that describe the job rather than configure the program
JOB_KEYS = ("method", "why")


def program_seed(seed: int) -> int:
    """The run's ``--seed`` (any whole number) as the program's seed, which
    the program keeps in 32 signed bits."""
    return int(seed) % (2 ** 31 - 1)


def make_job(config: Dict[str, Any], traffic: Dict[str, Any]) -> Job:
    return job_for(config, traffic, REF_BATCH)


def make_data(config: Dict[str, Any], job: Job, seed: int) -> FleetData:
    data = config["data"]
    return make_fleet_data(tuple(config["input_shape"]), job.n_classes,
                           job.n_clouds, job.clients_per_cloud,
                           n_samples=data["n_samples"],
                           samples_per_client=data["samples_per_client"],
                           ref_samples=data["ref_samples"],
                           alpha=data["dirichlet_alpha"], seed=seed)


def host_tree(tree) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v) for k, v in tree.items()}


def make_flconfig(config: Dict[str, Any], traffic: Dict[str, Any]):
    """The program's ``FLConfig``: every traffic key but ``JOB_KEYS``, and
    the configuration's data sizes it reads. An unknown key is its error."""
    from repro.configs.base import FLConfig
    return FLConfig(**{k: v for k, v in traffic.items() if k not in JOB_KEYS},
                    ref_samples=config["data"]["ref_samples"],
                    dirichlet_alpha=config["data"]["dirichlet_alpha"])


def build_server(config: Dict[str, Any], traffic: Dict[str, Any],
                 data: FleetData, seed: int):
    """``FLServer`` on the fleet's data, routed by ``engine="auto"``."""
    from repro.data.pipeline import FederatedData
    from repro.federated import FLServer
    from repro.federated.simulation import make_topology

    flcfg = make_flconfig(config, traffic)
    fed = FederatedData(client_x=data.client_x, client_y=data.client_y,
                        ref_x=data.ref_x, ref_y=data.ref_y,
                        test_x=data.test_x, test_y=data.test_y,
                        n_classes=data.n_classes)
    return FLServer(flcfg, make_topology(flcfg), fed, method=traffic["method"],
                    seed=seed, engine="auto")


def residual_norm(server) -> float:
    """L2 of the client-uplink error-feedback table (0 when there is
    none)."""
    res = getattr(getattr(server, "_eng_state", None), "res_client", None)
    if res is None or res.size == 0:
        return 0.0
    return float(jnp.sqrt(jnp.sum(jnp.square(res))))


def first_rounds(server, rounds: int) -> List[Dict[str, Any]]:
    """Drive the server through its first ``rounds`` rounds by its own
    ``run_round`` and keep what each produced, on the host."""
    out = [{"params": host_tree(server.params)}]
    for t in range(rounds):
        m = server.run_round(t)
        out.append({"delivered": np.asarray(m.selected, bool),
                    "intra_bytes": float(m.extra["intra_bytes"]),
                    "cross_bytes": float(m.extra["cross_bytes"]),
                    "dollars": float(m.cost),
                    "rep": np.asarray(m.reputation),
                    "params": host_tree(server.params),
                    "res_client_norm": residual_norm(server)})
    return out


def device_summary(chips: int) -> Dict[str, Any]:
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}
