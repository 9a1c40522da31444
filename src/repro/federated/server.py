"""Server-side orchestration of Algorithm 1 at simulation scale, plus
baseline servers (FedAvg / Krum / Trimmed-Mean / Median / FLTrust) sharing
the same round loop so Table I / Fig. 2-4 comparisons are apples-to-apples.

``FLServer`` is a thin stateful wrapper over the device-resident round
engine (``repro.federated.engine``): when the (method, attack, scenario)
combination is jittable, each ``run_round`` is ONE jitted device call on
a ``RoundState`` pytree; scenarios with host-only hooks (or dropout with
an order-statistic aggregator) transparently fall back to the legacy
host loop below, which remains the reference implementation of the
per-round protocol.
"""
from __future__ import annotations

from dataclasses import dataclass, field, fields
from functools import lru_cache
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from repro.compress import ef_step, policy_from_flcfg
from repro.configs.base import FLConfig
from repro.core import (CloudTopology, CostModel, ReputationState,
                        apply_update_attack, cost_trustfl_aggregate,
                        coordinate_median, fedavg, fltrust, krum,
                        select_clients, trimmed_mean)
from repro.core.selection import exploration_quota, selected_count
from repro.core.fl_types import RoundMetrics
from repro.data.pipeline import FederatedData
from repro.federated import client as client_mod
from repro.federated import engine as engine_mod
from repro.federated.engine import last_layer_spec, ravel_rows, tree_l2
from repro.scenarios.base import Scenario
from repro.telemetry import spans
from repro.telemetry.schema import RunContext

Array = jax.Array

_REF_BATCH = engine_mod.REF_BATCH  # reference LocalTrain batch

# the host loop's RoundState digest: one tiny jitted reduce over the
# params pytree — the same function the device engines run in-graph
_tree_l2_jit = jax.jit(tree_l2)


@lru_cache(maxsize=None)
def _jitted_trainers(epochs: int, batch: int, lr: float
                     ) -> Tuple[Callable, Callable]:
    """Shared jit-of-vmap trainers keyed by the training schedule, so
    every server with the same (epochs, batch, lr) reuses one compiled
    executable per data shape instead of retracing per FLServer — the
    scenario × method test matrix instantiates dozens of servers."""
    train_sel = jax.jit(jax.vmap(
        lambda p, x, y, k: client_mod.local_train(
            p, x, y, k, epochs=epochs, batch=batch, lr=lr),
        in_axes=(None, 0, 0, 0)))
    # reference LocalTrain uses the SAME schedule as clients so the
    # Eq. 12 rescale preserves the effective server step size
    train_refs = jax.jit(jax.vmap(
        lambda p, x, y, k: client_mod.local_train(
            p, x, y, k, epochs=epochs, batch=_REF_BATCH, lr=lr),
        in_axes=(None, 0, 0, None)))
    return train_sel, train_refs


@dataclass
class FLServer:
    """One server object per method; ``method`` picks the aggregation.

    ``engine`` selects the round driver: ``"auto"`` (mesh-sharded engine
    when >1 device is visible and the combination supports it, else the
    single-device scan engine when jittable, else the host loop),
    ``"shard"`` (force the ``("cloud", "client")`` mesh engine; raises
    if unsupported), ``"jit"`` (force the scan engine; raises if
    unsupported), ``"host"`` (force the legacy loop — reference
    semantics, used by the engine benchmark baseline). Routing lives in
    ``engine.resolve_engine``.
    """
    flcfg: FLConfig
    topo: CloudTopology
    data: FederatedData
    method: str = "cost_trustfl"
    seed: int = 0
    # optional adversary/environment scenario (repro.scenarios): its
    # hooks are the ONLY extension points run_round exposes — pricing
    # (round_start), delivery failures (delivered), per-round active
    # malice (active_malicious)
    scenario: Optional[Scenario] = None
    engine: str = "auto"
    # optional telemetry recorder (repro.telemetry.Telemetry or any
    # object with emit(dict)): run_start on construction, a round event
    # per run_round (identical across drivers given identical round
    # outputs), compile/execute spans; run_id defaults to
    # "<method>-s<seed>" so re-runs produce byte-comparable streams
    telemetry: Optional[Any] = None
    run_id: Optional[str] = None

    def __post_init__(self):
        key = jax.random.PRNGKey(self.seed)
        shape = self.data.client_x.shape[2:]
        self.params = client_mod.cnn_init(key, shape, self.data.n_classes)
        self.rep = ReputationState.init(self.topo.n_clients)
        self.cost_model = CostModel(self.flcfg.c_intra, self.flcfg.c_cross)
        # Eq. 10 sees the hierarchical marginal cost (see CostModel);
        # the flat Eq. 2 prices are used for the baselines' accounting
        self.unit_costs = self.cost_model.hierarchical_unit_costs(self.topo)
        self.cum_cost = 0.0
        # ravel machinery cached ONCE: the unravel closure and the flat
        # size are pure functions of the params template, not the round
        flat0, self._unravel = ravel_pytree(self.params)
        self.d_params = int(flat0.size)
        self.malicious = engine_mod.draw_malicious(self.flcfg,
                                                   self.topo.n_clients,
                                                   self.seed)
        # the trust path's g^(L): derived from the template's leaf tail
        # (not a hardcoded fc2_* name), with static flat-slice indices
        self._ll_spec = last_layer_spec(self.params)
        self._ll_idx = jnp.asarray(self._ll_spec.flat_idx)
        self._poisoned_y = engine_mod.poison_labels(
            self.flcfg, self.data, self.malicious, self.seed)
        self.history: List[RoundMetrics] = []
        # per-link gradient compression (repro.compress): codec per link
        # class, lazy error-feedback residual buffers per sender
        self.link_policy = policy_from_flcfg(self.flcfg)
        self._res_client: Optional[Array] = None    # (N, D) client uplinks
        self._res_edge: Optional[Array] = None      # (K, D) edge uplinks
        # multi-feature trust state (trust_features="multi"): the (F,)
        # separability EMA carried across rounds + the last round's
        # softmax mixing weights (telemetry)
        self._feat_sep: Optional[Array] = None
        self._feat_weights: Optional[np.ndarray] = None
        self.cum_intra_bytes = 0.0
        self.cum_cross_bytes = 0.0
        # jit the hot paths ONCE, shared across servers with the same
        # schedule (re-tracing per round — or per server in a scenario
        # matrix — dominates runtime on CPU otherwise)
        fl = self.flcfg
        self._train_selected, self._train_refs = _jitted_trainers(
            fl.local_epochs, fl.local_batch, fl.lr)
        # device engines: compiled programs are shared across servers
        # with the same static config (lru_cache), state/data live on
        # device; the sharded and scan engines duck-type the same
        # step/host_round_accounting surface, so run_round below is
        # driver-agnostic
        self._eng = None
        resolved = engine_mod.resolve_engine(self.engine, fl, self.topo,
                                             self.method, self.scenario)
        if resolved == "shard":
            from repro.federated import sharded as sharded_mod
            self._eng = sharded_mod.engine_for(fl, self.topo, self.data,
                                               self.method, self.scenario)
            self._eng_data = self._eng.stage_data(
                engine_mod.make_client_data(
                    fl, self.topo, self.data, self.seed,
                    malicious=self.malicious, poisoned_y=self._poisoned_y))
            self._eng_state = self._eng.init_state(self.seed)
        elif resolved == "jit":
            static = engine_mod.static_from(
                fl, self.topo, self.method, self.scenario,
                input_shape=shape, n_classes=self.data.n_classes)
            self._eng = engine_mod.compiled(static)
            self._eng_data = engine_mod.make_client_data(
                fl, self.topo, self.data, self.seed,
                malicious=self.malicious, poisoned_y=self._poisoned_y)
            self._eng_state = self._eng.init_state(self.seed)
        self.engine_resolved = resolved
        self._stepped = False                 # first run_round compiles
        self._telemetry_ctx: Optional[RunContext] = None
        if self.telemetry is not None:
            hier = self.method == "cost_trustfl"
            h = engine_mod.hooks_of(self.scenario)
            quota = exploration_quota(fl.cost_lambda) if hier else 0
            m_total = selected_count(self.topo.n_clients,
                                     fl.clients_per_round, quota,
                                     self.topo.cloud_of)
            cp, ep = self._link_payloads(hier)
            self._telemetry_ctx = RunContext(
                self.telemetry, engine=resolved,
                run_id=(self.run_id if self.run_id is not None
                        else f"{self.method}-s{self.seed}"),
                method=self.method, attack=fl.attack, seed=self.seed,
                topo=self.topo, d_params=self.d_params,
                hierarchical=hier, m_selected=m_total,
                malicious=self.malicious, client_payload=cp,
                edge_payload=ep, c_intra=fl.c_intra, c_cross=fl.c_cross,
                price_multipliers=h.price_multipliers,
                malice_warmup=h.malice_warmup,
                scenario=(self.scenario.name if self.scenario is not None
                          else None),
                trust_features=fl.trust_features)
            self._telemetry_ctx.run_start(
                config={f.name: getattr(fl, f.name)
                        for f in fields(fl)})

    # -- selection (host path) -------------------------------------------------
    def _select(self, rng: np.random.Generator) -> np.ndarray:
        m = self.flcfg.clients_per_round
        if self.method == "cost_trustfl":
            quota = exploration_quota(self.flcfg.cost_lambda)
            return select_clients(np.array(self.rep.ema), self.unit_costs, m,
                                  per_cloud_min=quota,
                                  cloud_of=self.topo.cloud_of,
                                  cost_lambda=self.flcfg.cost_lambda, rng=rng)
        sel = np.zeros(self.topo.n_clients, bool)
        sel[rng.choice(self.topo.n_clients, m, replace=False)] = True
        return sel

    # -- reference updates (per-cloud trusted datasets) ------------------------
    def _reference_updates(self, key: Array) -> Any:
        return self._train_refs(self.params, jnp.asarray(self.data.ref_x),
                                jnp.asarray(self.data.ref_y), key)

    # -- per-link compression (repro.compress) ---------------------------------
    def _ef_rows(self, codec, flat_sel: Array, sel_ix: np.ndarray,
                 local_rows: np.ndarray, key: Array) -> Array:
        """Error-feedback round-trip the given rows of the selected-update
        matrix through ``codec``, persisting per-client residuals."""
        if codec.is_identity or local_rows.size == 0:
            return flat_sel
        if self._res_client is None:
            self._res_client = jnp.zeros(
                (self.topo.n_clients, flat_sel.shape[1]), jnp.float32)
        rows = jnp.asarray(sel_ix[local_rows])
        # rows carry their GLOBAL client ids into the codec so stochastic
        # noise is keyed per sender, identically to the device engines
        x_hat, new_res = ef_step(codec, flat_sel[local_rows],
                                 self._res_client[rows], key, rows)
        self._res_client = self._res_client.at[rows].set(new_res)
        return flat_sel.at[jnp.asarray(local_rows)].set(x_hat)

    def _compress_client_uplinks(self, flat_sel: Array, sel_ix: np.ndarray,
                                 key: Array) -> Array:
        """Apply each selected client's uplink codec. Under the hierarchy
        every client→edge hop is intra-cloud; on the flat baseline path a
        client's single hop is intra or cross by co-location."""
        lp = self.link_policy
        local = np.arange(sel_ix.size)
        if self.method == "cost_trustfl":
            return self._ef_rows(lp.intra, flat_sel, sel_ix, local, key)
        same = self.topo.cloud_of[sel_ix] == self.topo.aggregator_cloud
        flat_sel = self._ef_rows(lp.intra, flat_sel, sel_ix, local[same],
                                 jax.random.fold_in(key, 0))
        return self._ef_rows(lp.cross, flat_sel, sel_ix, local[~same],
                             jax.random.fold_in(key, 1))

    def _edge_transform(self, key: Array, sel: np.ndarray
                        ) -> Optional[Callable]:
        """Edge→global wire model for cost_trustfl_aggregate: the shared
        ``engine.build_edge_wire_fn`` EF closure (one source of truth
        across the host loop and both device engines — the key folds are
        part of the cross-engine parity contract), adapted to this
        loop's mutable residual buffer. ``key`` is the already-folded
        ``_FOLD_EDGE_WIRE`` stream. Inactive clouds (no selected
        clients — their aggregate row is the receiver-side reference
        fallback, nothing crosses the wire) pass through untouched and
        keep their residual, matching round_bytes which bills them zero
        bytes."""
        lp = self.link_policy
        if not lp.any_active:
            return None
        wire = engine_mod.build_edge_wire_fn(lp, self.topo.n_clouds,
                                             self.topo.aggregator_cloud)
        active = jnp.asarray(np.bincount(
            self.topo.cloud_of[np.asarray(sel, bool)],
            minlength=self.topo.n_clouds) > 0)[:, None]

        def transform(cloud_aggs: Array) -> Array:
            if self._res_edge is None:
                self._res_edge = jnp.zeros_like(cloud_aggs)
            out, self._res_edge = wire(cloud_aggs, self._res_edge, active,
                                       key)
            return out

        return transform

    def _link_payloads(self, hierarchical: bool
                       ) -> Tuple[np.ndarray, np.ndarray]:
        """Exact wire bytes per client uplink (N,) and edge uplink (K,)."""
        return self.link_policy.payload_vectors(self.topo, self.d_params,
                                                hierarchical=hierarchical)

    # -- one round --------------------------------------------------------------
    def run_round(self, t: int) -> RoundMetrics:
        # the ``round`` span is a profiler annotation on every call; with
        # telemetry its span event separates compile (the first round
        # traces + compiles the step) from steady-state execute
        phase = "execute" if self._stepped else "compile+execute"
        with spans.span("round", self._telemetry_ctx, phase=phase, t=t):
            metrics = (self._run_round_engine(t, phase)
                       if self._eng is not None
                       else self._run_round_host(t))
        self._stepped = True
        return metrics

    def _run_round_engine(self, t: int, phase: str) -> RoundMetrics:
        """Engine driver: one jitted device call, then host-side float64
        accounting from the delivered mask (byte-exact at any scale and
        bit-identical to the lax.scan driver, which reduces the same
        per-round masks). Its host time falls into profiler spans:
        ``host.dispatch`` (the step call), one ``host.fetch`` per
        device→host read, and ``host.account``."""
        ctx = self._telemetry_ctx

        def host(name: str):
            return spans.span(name, ctx, phase=phase, t=t)

        with host("host.dispatch"):
            state, out = self._eng.step(self._eng_state, self._eng_data, t)
        self._eng_state = state
        self.params = state.params
        self.rep = ReputationState(ema=state.rep_ema)
        with host("host.fetch"):
            delivered = np.asarray(out.delivered)
        with host("host.fetch"):
            reputation = np.array(state.rep_ema)
        if ctx is not None:
            with host("host.fetch"):
                params_l2 = float(out.params_l2)
            with host("host.fetch"):
                feat_weights = np.asarray(out.feat_weights)
        with host("host.account"):
            cost, intra_b, cross_b = self._eng.host_round_accounting(
                delivered[None], t0=t)[0]
            self.cum_cost += cost
            self.cum_intra_bytes += intra_b
            self.cum_cross_bytes += cross_b
            metrics = RoundMetrics(round=t, cost=cost,
                                   cum_cost=self.cum_cost,
                                   selected=delivered, reputation=reputation,
                                   extra={"intra_bytes": intra_b,
                                          "cross_bytes": cross_b})
            if ctx is not None:
                # same raw inputs and accounting floats as the scan
                # stream collector → byte-identical round events across
                # drivers
                ctx.round(t, delivered, reputation, params_l2,
                          cost=float(cost), intra_bytes=float(intra_b),
                          cross_bytes=float(cross_b),
                          feat_weights=(feat_weights if feat_weights.size
                                        else None))
            self.history.append(metrics)
        return metrics

    def _run_round_host(self, t: int) -> RoundMetrics:
        """Legacy host loop — the reference protocol implementation, and
        the only driver for scenarios with host-only hooks."""
        rng = np.random.default_rng(self.seed * 100003 + t)
        key = jax.random.PRNGKey(self.seed * 7919 + t)
        sc = self.scenario
        if sc is not None:
            # environment mutation (e.g. dynamic egress pricing) BEFORE
            # selection, so Eq. 10 and this round's $ see the same prices
            sc.round_start(self, t, rng)
        sel = self._select(rng)
        if sc is not None:
            # dropout/stragglers: selected clients that never deliver
            # neither train nor put bytes on the wire
            sel = sc.delivered(self, t, rng, sel)
        sel_ix = np.nonzero(sel)[0]

        # local training for selected clients (vmap over clients)
        keys = jax.random.split(key, self.topo.n_clients)
        upd_tree = self._train_selected(
            self.params, jnp.asarray(self.data.client_x[sel_ix]),
            jnp.asarray(self._poisoned_y[sel_ix]), keys[sel_ix])

        flat_sel = ravel_rows(upd_tree)

        # update-level attacks on the round's ACTIVE malicious clients
        # (scenarios may gate the static set, e.g. intermittent sleepers)
        malicious = (self.malicious if sc is None
                     else sc.active_malicious(self, t))
        mal_sel = jnp.asarray(malicious[sel_ix])
        flat_sel = apply_update_attack(
            self.flcfg.attack, flat_sel, mal_sel, key,
            sigma=self.flcfg.gaussian_sigma, scale=self.flcfg.attack_scale,
            z=self.flcfg.attack_z)

        n = self.topo.n_clients
        lp = self.link_policy
        # does any client-uplink codec actually distort flat_sel? (under
        # the hierarchy every client hop is intra; the default cross_only
        # policy leaves them untouched)
        client_wire_active = (not lp.intra.is_identity
                              if self.method == "cost_trustfl"
                              else lp.any_active)
        if client_wire_active:
            # client uplink wire: compress after the (sender-side) attack;
            # everything downstream — trust, Shapley, aggregation — sees
            # only the decompressed updates, incl. the last-layer slice
            flat_sel = self._compress_client_uplinks(
                flat_sel, sel_ix, jax.random.fold_in(key, 211))
        # the trust path's last-layer slice is ALWAYS taken from the
        # attacked (and possibly compressed) flat matrix, so statistics-
        # based adversaries (ALIE / IPM / min-max) present one consistent
        # view to trust scoring and aggregation
        ll_sel = flat_sel[:, self._ll_idx]

        # scatter to full (N, D) with zeros for non-selected
        flat = jnp.zeros((n, flat_sel.shape[1]), flat_sel.dtype
                         ).at[jnp.asarray(sel_ix)].set(flat_sel)
        ll = jnp.zeros((n, ll_sel.shape[1]), ll_sel.dtype
                       ).at[jnp.asarray(sel_ix)].set(ll_sel)

        # aggregate
        update_flat, hierarchical = self._aggregate(flat, ll, key, sel)

        # apply: w <- w - eta * g   (server_lr; g is a model delta)
        delta = self._unravel(update_flat * self.flcfg.server_lr)
        self.params = jax.tree.map(lambda w, g: w - g, self.params, delta)

        # cost accounting (Eq. 1 / Eq. 3 structure) at exact wire bytes
        client_payload, edge_payload = self._link_payloads(hierarchical)
        intra_b, cross_b = self.cost_model.round_bytes(
            self.topo, sel, self.d_params, hierarchical=hierarchical,
            client_payload=client_payload, edge_payload=edge_payload)
        cost = self.cost_model.round_cost(
            self.topo, sel, self.d_params, hierarchical=hierarchical,
            client_payload=client_payload, edge_payload=edge_payload)
        self.cum_cost += cost
        self.cum_intra_bytes += intra_b
        self.cum_cross_bytes += cross_b
        metrics = RoundMetrics(round=t, cost=cost, cum_cost=self.cum_cost,
                               selected=sel,
                               reputation=np.array(self.rep.ema),
                               extra={"intra_bytes": intra_b,
                                      "cross_bytes": cross_b})
        if self._telemetry_ctx is not None:
            # explicit $ /bytes: only this loop knows prices a host hook
            # may have mutated (self.cost_model); digest via the same
            # tree_l2 the device engines run in-graph
            self._telemetry_ctx.round(
                t, sel, metrics.reputation,
                float(_tree_l2_jit(self.params)),
                cost=float(cost), intra_bytes=float(intra_b),
                cross_bytes=float(cross_b),
                feat_weights=self._feat_weights)
        self.history.append(metrics)
        return metrics

    def _aggregate(self, flat: Array, ll: Array, key: Array,
                   sel: np.ndarray) -> Tuple[Array, bool]:
        method = self.method
        sel_j = jnp.asarray(sel)
        if method == "cost_trustfl":
            ref_tree = self._reference_updates(key)
            ref_flat = ravel_rows(ref_tree)
            ref_ll = ref_flat[:, self._ll_idx]
            res = cost_trustfl_aggregate(
                flat, ll, ref_flat, ref_ll,
                jnp.asarray(self.topo.cloud_of), sel_j, self.rep,
                gamma=self.flcfg.ema_gamma,
                cloud_transform=self._edge_transform(
                    jax.random.fold_in(key, 223), sel),
                trust_features=self.flcfg.trust_features,
                feat_sep=self._feat_sep)
            self.rep = res.reputation
            if res.feat_sep is not None:
                self._feat_sep = res.feat_sep
                self._feat_weights = np.asarray(res.feat_weights)
            return res.update, True
        sel_ix = jnp.nonzero(sel_j, size=int(sel.sum()))[0]
        u = flat[sel_ix]
        if method == "fedavg":
            return fedavg(u), False
        if method == "krum":
            f = int(self.flcfg.malicious_frac * u.shape[0])
            return krum(u, f, multi=max(1, u.shape[0] - f - 2)), False
        if method == "trimmed_mean":
            return trimmed_mean(u, trim_frac=self.flcfg.malicious_frac / 2), False
        if method == "median":
            return coordinate_median(u), False
        if method == "fltrust":
            ref_tree = self._reference_updates(key)
            ref_flat = ravel_rows(ref_tree)
            return fltrust(u, jnp.mean(ref_flat, axis=0)), False
        raise ValueError(method)

    # -- eval -------------------------------------------------------------------
    def evaluate(self) -> float:
        return client_mod.accuracy(self.params,
                                   jnp.asarray(self.data.test_x),
                                   jnp.asarray(self.data.test_y))

    # -- telemetry hooks (no-ops when no recorder is attached) ------------------
    def record_eval(self, t: int, accuracy: float,
                    loss: Optional[float] = None) -> None:
        if self._telemetry_ctx is not None:
            self._telemetry_ctx.eval(t, accuracy, loss)

    def finish_telemetry(self) -> None:
        if self._telemetry_ctx is not None:
            self._telemetry_ctx.run_end()
