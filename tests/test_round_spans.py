"""Round phases as the profiler sees them: the ``jax.named_scope`` names
in the compiled step of both device engines (HLO ``op_name`` metadata),
and the host spans of ``FLServer.run_round`` as telemetry events."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.configs.base import FLConfig
from repro.federated import FLServer, make_data, make_topology
from repro.telemetry import ListSink, Telemetry

_TOPK = dict(n_clouds=3, clients_per_cloud=3, clients_per_round=4,
             local_epochs=1, local_batch=4, ref_samples=12,
             attack="sign_flip", malicious_frac=0.3, compressor="topk",
             compress_ratio=0.1, link_policy="all")


@pytest.fixture(scope="module")
def topk_fleet():
    fl = FLConfig(**_TOPK)
    data = make_data(fl, "cifar10", seed=0, n_samples=300,
                     samples_per_client=8)
    return fl, data


def test_compile_cache_is_keyed_on_scope_metadata():
    """A cached executable carries the metadata it was compiled with, so
    the cache key must include it for a profile to show this build's
    scope names."""
    import jax

    import repro.federated.engine  # noqa: F401
    assert jax.config.jax_compilation_cache_include_metadata_in_key


def test_scan_step_names_the_nested_aggregate_scopes(topk_fleet):
    fl, data = topk_fleet
    srv = FLServer(fl, make_topology(fl), data, method="cost_trustfl",
                   seed=0, engine="jit")
    hlo = srv._eng.step.lower(srv._eng_state, srv._eng_data,
                              0).compile().as_text()
    for scope in ("round.train", "round.compress",
                  "round.aggregate/ref_train", "round.aggregate/edge_codec"):
        assert scope in hlo, scope


_MESH_SCRIPT = textwrap.dedent("""
    import json, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax.numpy as jnp
    from repro.configs.base import FLConfig
    from repro.federated import FLServer, make_data, make_topology
    fl = FLConfig(**json.loads(os.environ["MESH_FL"]))
    data = make_data(fl, "cifar10", seed=0, n_samples=300,
                     samples_per_client=8)
    srv = FLServer(fl, make_topology(fl), data, method="cost_trustfl",
                   seed=0, engine="shard")
    step = next(c.cell_contents for c in srv._eng.step.__closure__
                if hasattr(c.cell_contents, "lower"))
    hlo = step.lower(srv._eng_state, srv._eng_data,
                     jnp.asarray(0, jnp.int32)).compile().as_text()
    scopes = ["round.select", "round.train", "round.attack",
              "round.compress", "round.aggregate", "round.account",
              "round.aggregate/ref_train", "round.aggregate/edge_codec"]
    print("RESULT" + json.dumps({s: s in hlo for s in scopes}))
""")


def test_mesh_step_names_the_round_scopes():
    """The mesh engine on four CPU devices, in a process of its own (the
    device count is fixed before JAX starts)."""
    fl = dict(_TOPK, n_clouds=4, clients_per_cloud=4, clients_per_round=8)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["MESH_FL"] = json.dumps(fl)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + [p for p in [env.get("PYTHONPATH")] if p])
    proc = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    found = json.loads(line[len("RESULT"):])
    assert all(found.values()), found


@pytest.mark.parametrize("engine,children", [
    ("jit", ["host.dispatch", "host.fetch", "host.fetch", "host.fetch",
             "host.fetch", "host.account"]),
    ("host", []),
])
def test_round_span_events(topk_fleet, engine, children):
    """With a recorder attached, the device engine's host spans emit
    ``span`` events under the round's phase and ``t`` (four reads: the
    delivered mask, the reputations, the params digest and the feature
    weights), each before the ``round`` span that encloses it; the host
    loop emits the ``round`` span alone."""
    fl, data = topk_fleet
    sink = ListSink()
    with Telemetry(sink) as tel:
        srv = FLServer(fl, make_topology(fl), data, method="cost_trustfl",
                       seed=0, engine=engine, telemetry=tel)
        for t in range(2):
            srv.run_round(t)
    spans = [e for e in sink.events if e["event"] == "span"]
    per_round = children + ["round"]
    assert [s["name"] for s in spans] == per_round * 2
    for s, (t, phase) in zip(spans, [(0, "compile+execute")] * len(per_round)
                             + [(1, "execute")] * len(per_round)):
        assert (s["t"], s["phase"]) == (t, phase)
        assert s["seconds"] >= 0.0
    assert sum(e["event"] == "round" for e in sink.events) == 2
