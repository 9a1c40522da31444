"""End-to-end simulation harness reproducing the paper's experimental
protocol (3 clouds x 30 clients, Dirichlet non-IID, 4 attacks,
6 methods)."""
from __future__ import annotations

import time
from dataclasses import dataclass, fields, replace
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FLConfig
from repro.core.fl_types import CloudTopology
from repro.data.pipeline import FederatedData, build_federated
from repro.data.synthetic import make_cifar10_like, make_femnist_like
from repro.federated import client as client_mod
from repro.federated import engine as engine_mod
from repro.federated.server import FLServer
from repro.scenarios import Scenario, get_scenario
from repro.telemetry import taps as taps_mod
from repro.telemetry.schema import RunContext
from repro.telemetry.taps import TapSpec

ScenarioLike = Union[str, Scenario, None]


@dataclass
class SimResult:
    method: str
    attack: str
    accuracy: List[float]
    rounds: List[int]
    final_accuracy: Optional[float]   # None when no eval ran (rounds=0)
    total_cost: float
    reputation: Optional[np.ndarray] = None
    malicious: Optional[np.ndarray] = None
    intra_bytes: float = 0.0          # cumulative wire bytes, intra-class
    cross_bytes: float = 0.0          # cumulative wire bytes, cross-cloud
    scenario: Optional[str] = None    # registry name when one was run


def make_topology(flcfg: FLConfig) -> CloudTopology:
    return CloudTopology.even(flcfg.n_clouds, flcfg.clients_per_cloud)


def make_data(flcfg: FLConfig, dataset: str = "cifar10", seed: int = 0,
              n_samples: int = 12000, samples_per_client: int = 96
              ) -> FederatedData:
    topo = make_topology(flcfg)
    ds = (make_cifar10_like(n_samples, seed) if dataset == "cifar10"
          else make_femnist_like(n_samples, seed))
    return build_federated(ds, topo, alpha=flcfg.dirichlet_alpha,
                           samples_per_client=samples_per_client,
                           ref_samples=flcfg.ref_samples, seed=seed)


def _resolve_scenario(scenario: ScenarioLike) -> Optional[Scenario]:
    return get_scenario(scenario) if isinstance(scenario, str) else scenario


def _engine_context(telemetry: Any, *, engine_name: str, eng, flcfg: FLConfig,
                    topo: CloudTopology, method: str,
                    scenario: Optional[Scenario], seed: int,
                    malicious: np.ndarray, rounds: int) -> RunContext:
    """RunContext for a device-engine driver (scan or sharded), with
    ``run_start`` already emitted — one construction path so the batch,
    sharded and streaming drivers describe runs identically."""
    st = eng.static
    ctx = RunContext(
        telemetry, engine=engine_name, run_id=f"{method}-s{seed}",
        method=method, attack=flcfg.attack, seed=seed, topo=topo,
        d_params=eng.d_params, hierarchical=st.hierarchical,
        m_selected=engine_mod.selected_total(st), malicious=malicious,
        client_payload=eng.client_payload, edge_payload=eng.edge_payload,
        c_intra=st.c_intra, c_cross=st.c_cross,
        price_multipliers=st.price_multipliers,
        malice_warmup=st.malice_warmup,
        scenario=scenario.name if scenario is not None else None,
        trust_features=flcfg.trust_features)
    ctx.run_start(rounds=rounds,
                  config={f.name: getattr(flcfg, f.name)
                          for f in fields(flcfg)})
    return ctx


def _replay_rounds(ctx: RunContext, delivered: np.ndarray,
                   reps: np.ndarray, params_l2: np.ndarray,
                   feat_weights: Optional[np.ndarray] = None) -> None:
    """Emit round events from stacked (T, ...) RoundOut arrays — the
    post-run path for drivers that cannot stream (vmapped batches, the
    sharded engine whose per-shard callbacks would duplicate events)."""
    for t in range(len(delivered)):
        ctx.round(t, delivered[t], reps[t], float(params_l2[t]),
                  feat_weights=(feat_weights[t] if feat_weights is not None
                                else None))


def run_simulation(flcfg: FLConfig, *, method: Optional[str] = None,
                   scenario: ScenarioLike = None,
                   dataset: str = "cifar10", rounds: Optional[int] = None,
                   eval_every: int = 5, seed: int = 0,
                   data: Optional[FederatedData] = None,
                   engine: str = "auto",
                   telemetry: Any = None,
                   verbose: bool = False) -> SimResult:
    """Run one (method, scenario) simulation.

    ``scenario`` — a ``repro.scenarios`` registry name or ``Scenario``:
    its FLConfig overrides are applied first (idempotent, so callers that
    already applied them can pass both) and its hooks ride along on the
    server. ``method`` defaults to ``flcfg.aggregator``; an explicit
    argument wins over the config field. ``engine`` is forwarded to
    ``FLServer`` (round-driver routing — see ``engine.resolve_engine``).
    ``telemetry`` — an optional ``repro.telemetry.Telemetry`` recorder:
    the server emits run_start / per-round / span events, this harness
    adds eval events and the closing run_end.
    """
    scenario = _resolve_scenario(scenario)
    if scenario is not None:
        flcfg = scenario.apply(flcfg)
    method = flcfg.aggregator if method is None else method
    rounds = rounds if rounds is not None else flcfg.rounds
    topo = make_topology(flcfg)
    data = data if data is not None else make_data(flcfg, dataset, seed)
    server = FLServer(flcfg, topo, data, method=method, seed=seed,
                      scenario=scenario, engine=engine,
                      telemetry=telemetry)

    accs, ticks = [], []
    for t in range(rounds):
        server.run_round(t)
        if (t + 1) % eval_every == 0 or t == rounds - 1:
            acc = server.evaluate()
            accs.append(acc)
            ticks.append(t + 1)
            server.record_eval(t, acc)
            if verbose:
                print(f"[{method}/{flcfg.attack}] round {t+1:4d} "
                      f"acc={acc:.4f} cum_cost=${server.cum_cost:.4f}")
    server.finish_telemetry()
    # rounds=0 yields no evals -> final_accuracy None. FLServer always
    # carries rep today; the getattr keeps SimResult construction working
    # for server implementations without reputation state.
    rep = getattr(server, "rep", None)
    return SimResult(method=method, attack=flcfg.attack, accuracy=accs,
                     rounds=ticks,
                     final_accuracy=accs[-1] if accs else None,
                     total_cost=server.cum_cost,
                     reputation=(np.array(rep.ema) if rep is not None
                                 else None),
                     malicious=server.malicious,
                     intra_bytes=server.cum_intra_bytes,
                     cross_bytes=server.cum_cross_bytes,
                     scenario=scenario.name if scenario is not None else None)


def run_simulation_batch(flcfg: FLConfig, *, seeds: Sequence[int],
                         method: Optional[str] = None,
                         scenario: ScenarioLike = None,
                         dataset: str = "cifar10",
                         rounds: Optional[int] = None,
                         data: Optional[FederatedData] = None,
                         telemetry: Any = None) -> List[SimResult]:
    """Device-resident multi-seed sweep: ``lax.scan`` over rounds,
    ``vmap`` over seeds — the whole grid cell is one jitted device call.

    Semantics match ``run_simulation`` driven by the engine-backed
    ``FLServer`` (a single-seed batch is bit-identical to the host loop;
    see tests/test_determinism.py), except that accuracy is evaluated
    once, after the final round. Each seed gets its own data partition,
    model init and adversary draw unless a shared ``data`` is passed.
    Requires a jittable (method, attack, scenario) combination — host-
    hook scenarios raise (run them through ``run_simulation``).

    ``telemetry``: a single-seed batch streams its round events LIVE out
    of the running scan (ordered ``jax.debug.callback`` tap — and those
    events are byte-identical to the per-round ``FLServer`` driver's);
    multi-seed batches run untapped (ordered callbacks cannot cross
    vmap) and replay per-seed events from the stacked outputs after the
    device call.
    """
    scenario = _resolve_scenario(scenario)
    if scenario is not None:
        flcfg = scenario.apply(flcfg)
    method = flcfg.aggregator if method is None else method
    rounds = rounds if rounds is not None else flcfg.rounds
    topo = make_topology(flcfg)
    datas = [data if data is not None else make_data(flcfg, dataset, s)
             for s in seeds]
    static = engine_mod.static_from(
        flcfg, topo, method, scenario,
        input_shape=datas[0].client_x.shape[2:],
        n_classes=datas[0].n_classes)
    eng = engine_mod.compiled(static)
    if data is not None:
        # stage the shared sample arrays on device ONCE; only labels
        # (poisoning) and the adversary draw differ per seed
        sx, rx, ry = (jnp.asarray(data.client_x), jnp.asarray(data.ref_x),
                      jnp.asarray(data.ref_y))
        mals = [engine_mod.draw_malicious(flcfg, topo.n_clients, s)
                for s in seeds]
        dev = [engine_mod.ClientData(
                   client_x=sx,
                   client_y=jnp.asarray(
                       engine_mod.poison_labels(flcfg, data, m, s)),
                   ref_x=rx, ref_y=ry, malicious=jnp.asarray(m))
               for m, s in zip(mals, seeds)]
    else:
        dev = [engine_mod.make_client_data(flcfg, topo, d, s)
               for d, s in zip(datas, seeds)]
    states = [eng.init_state(s) for s in seeds]
    ctxs = None
    if telemetry is not None:
        ctxs = [_engine_context(telemetry, engine_name="jit", eng=eng,
                                flcfg=flcfg, topo=topo, method=method,
                                scenario=scenario, seed=s,
                                malicious=np.asarray(dev[i].malicious),
                                rounds=rounds)
                for i, s in enumerate(seeds)]
    streamed = False

    stack = lambda *xs: np.stack([np.asarray(x) for x in xs])
    t0 = time.perf_counter()
    if rounds == 0:
        finals, delivered, reps, pl2, fw = states, None, None, None, None
    elif len(seeds) == 1:
        # unbatched scan: bit-identical to the per-round engine driver
        if ctxs is not None:
            # live stream: compile the tapped executable and install the
            # collector for the duration of the device call — collecting()
            # drains the async callback queue before uninstalling
            ctx = ctxs[0]
            tapped = engine_mod.compiled(static, TapSpec(enabled=True))
            collect = lambda t, out: ctx.round(
                int(t), np.asarray(out.delivered), np.asarray(out.rep),
                float(out.params_l2),
                feat_weights=(np.asarray(out.feat_weights)
                              if np.asarray(out.feat_weights).size
                              else None))
            with taps_mod.collecting(collect):
                fin, outs = tapped.run(states[0], dev[0], rounds)
                jax.block_until_ready(outs.delivered)
            streamed = True
        else:
            fin, outs = eng.run(states[0], dev[0], rounds)
        finals = [fin]
        delivered = np.asarray(outs.delivered)[None]       # (1, T, N)
        reps = np.asarray(outs.rep)[None]
        pl2 = np.asarray(outs.params_l2)[None]
        fw = np.asarray(outs.feat_weights)[None]           # (1, T, F|0)
    elif data is not None:
        # one dataset shared across seeds: broadcast the sample arrays
        # (one device copy) and stack only the per-seed leaves (poisoned
        # labels + adversary draw)
        shared = engine_mod.ClientData(
            client_x=dev[0].client_x,
            client_y=stack(*[d.client_y for d in dev]),
            ref_x=dev[0].ref_x, ref_y=dev[0].ref_y,
            malicious=stack(*[d.malicious for d in dev]))
        fin, outs = eng.run_batch_shared(jax.tree.map(stack, *states),
                                         shared, rounds)
        finals = [jax.tree.map(lambda x, i=i: x[i], fin)
                  for i in range(len(seeds))]
        delivered = np.asarray(outs.delivered)             # (S, T, N)
        reps = np.asarray(outs.rep)
        pl2 = np.asarray(outs.params_l2)
        fw = np.asarray(outs.feat_weights)                 # (S, T, F|0)
    else:
        fin, outs = eng.run_batch(jax.tree.map(stack, *states),
                                  jax.tree.map(stack, *dev), rounds)
        finals = [jax.tree.map(lambda x, i=i: x[i], fin)
                  for i in range(len(seeds))]
        delivered = np.asarray(outs.delivered)             # (S, T, N)
        reps = np.asarray(outs.rep)
        pl2 = np.asarray(outs.params_l2)
        fw = np.asarray(outs.feat_weights)                 # (S, T, F|0)
    if ctxs is not None:
        dt = time.perf_counter() - t0
        for ctx in ctxs:
            ctx.span("engine.run", dt, phase="compile+execute")

    results = []
    for i, s in enumerate(seeds):
        fin = finals[i]
        if rounds == 0:
            acc, ticks, cost, ib, cb = [], [], 0.0, 0.0, 0.0
            rep = np.array(fin.rep_ema)
        else:
            a = client_mod.accuracy(fin.params,
                                    jnp.asarray(datas[i].test_x),
                                    jnp.asarray(datas[i].test_y))
            acc, ticks = [a], [rounds]
            # byte-exact float64 accounting from the delivered masks —
            # the same reduction the per-round FLServer driver performs
            rows = eng.host_round_accounting(delivered[i])
            cost, ib, cb = (float(rows[:, 0].sum()),
                            float(rows[:, 1].sum()),
                            float(rows[:, 2].sum()))
            rep = reps[i, -1]
        if ctxs is not None:
            ctx = ctxs[i]
            if rounds > 0 and not streamed:
                _replay_rounds(ctx, delivered[i], reps[i], pl2[i],
                               fw[i] if fw is not None and fw.shape[-1]
                               else None)
            if acc:
                ctx.eval(rounds - 1, float(acc[0]))
            ctx.run_end()
        results.append(SimResult(
            method=method, attack=flcfg.attack, accuracy=acc, rounds=ticks,
            final_accuracy=acc[-1] if acc else None, total_cost=cost,
            reputation=np.array(rep),
            malicious=np.asarray(dev[i].malicious),
            intra_bytes=ib, cross_bytes=cb,
            scenario=scenario.name if scenario is not None else None))
    return results


def run_simulation_sharded(flcfg: FLConfig, *,
                           method: Optional[str] = None,
                           scenario: ScenarioLike = None,
                           dataset: str = "cifar10",
                           rounds: Optional[int] = None, seed: int = 0,
                           data: Optional[FederatedData] = None,
                           n_devices: Optional[int] = None,
                           telemetry: Any = None) -> SimResult:
    """One simulation on the mesh-sharded engine
    (``repro.federated.sharded``): clients laid out over a
    ``("cloud", "client")`` device mesh, Eq. 5–13 as a two-stage
    intra-cloud/cross-cloud reduction, the whole run ONE ``shard_map``'d
    ``lax.scan`` call.

    Semantics match ``run_simulation`` on the scan engine to documented
    fp tolerance (exactly for selection/delivery masks and byte/cost
    accounting; ~1e-4 relative for params/reputation, the bound
    tests/test_sharded.py enforces). Accuracy is evaluated once, after
    the final round. Raises with a clear reason for configurations the sharded
    engine refuses (matrix-shaped attacks/codecs, host-hook scenarios,
    populations that do not tile the device count).
    """
    from repro.federated import sharded as sharded_mod

    scenario = _resolve_scenario(scenario)
    if scenario is not None:
        flcfg = scenario.apply(flcfg)
    method = flcfg.aggregator if method is None else method
    rounds = rounds if rounds is not None else flcfg.rounds
    topo = make_topology(flcfg)
    data = data if data is not None else make_data(flcfg, dataset, seed)
    eng = sharded_mod.engine_for(flcfg, topo, data, method, scenario,
                                 n_devices=n_devices)
    malicious = engine_mod.draw_malicious(flcfg, topo.n_clients, seed)
    dev = eng.stage_data(engine_mod.make_client_data(
        flcfg, topo, data, seed, malicious=malicious))
    state = eng.init_state(seed)
    ctx = (None if telemetry is None else
           _engine_context(telemetry, engine_name="shard", eng=eng,
                           flcfg=flcfg, topo=topo, method=method,
                           scenario=scenario, seed=seed,
                           malicious=np.asarray(malicious), rounds=rounds))

    if rounds == 0:
        if ctx is not None:
            ctx.run_end()
        return SimResult(method=method, attack=flcfg.attack, accuracy=[],
                         rounds=[], final_accuracy=None, total_cost=0.0,
                         reputation=np.array(state.rep_ema),
                         malicious=malicious,
                         scenario=(scenario.name if scenario is not None
                                   else None))

    t0 = time.perf_counter()
    fin, outs = eng.run(state, dev, rounds)
    acc = client_mod.accuracy(fin.params, jnp.asarray(data.test_x),
                              jnp.asarray(data.test_y))
    if ctx is not None:
        # per-shard callbacks would emit one event per device; replay the
        # stacked RoundOut instead (digests match scan to ~1e-4)
        ctx.span("engine.run", time.perf_counter() - t0,
                 phase="compile+execute")
        sh_fw = np.asarray(outs.feat_weights)
        _replay_rounds(ctx, np.asarray(outs.delivered),
                       np.asarray(outs.rep), np.asarray(outs.params_l2),
                       sh_fw if sh_fw.shape[-1] else None)
        ctx.eval(rounds - 1, float(acc))
        ctx.run_end()
    # byte-exact float64 accounting from the delivered masks — the same
    # reduction every other engine driver performs
    rows = eng.host_round_accounting(np.asarray(outs.delivered))
    return SimResult(
        method=method, attack=flcfg.attack, accuracy=[acc], rounds=[rounds],
        final_accuracy=acc, total_cost=float(rows[:, 0].sum()),
        reputation=np.array(fin.rep_ema), malicious=malicious,
        intra_bytes=float(rows[:, 1].sum()),
        cross_bytes=float(rows[:, 2].sum()),
        scenario=scenario.name if scenario is not None else None)


def compare_methods(flcfg: FLConfig, methods: List[str], *,
                    scenario: ScenarioLike = None,
                    dataset: str = "cifar10", rounds: int = 30,
                    seed: int = 0, verbose: bool = False
                    ) -> Dict[str, SimResult]:
    """Run every method on ONE dataset/scenario so comparisons are
    apples-to-apples (shared data partition, shared scenario hooks)."""
    scenario = _resolve_scenario(scenario)
    if scenario is not None:
        flcfg = scenario.apply(flcfg)   # before make_data: overrides may
    data = make_data(flcfg, dataset, seed)  # change topology/partition
    return {m: run_simulation(flcfg, method=m, scenario=scenario,
                              dataset=dataset, rounds=rounds, seed=seed,
                              data=data, verbose=verbose)
            for m in methods}
