"""Device-resident round engine: the full Cost-TrustFL round as a pure
``round_step(state, t) -> (state, metrics)`` function, driven by
``lax.scan`` over rounds and ``vmap`` over seeds.

The host loop (``FLServer.run_round``) pays Python dispatch, numpy RNG
and host↔device syncs ~10 times per round; at simulation scale that
overhead dominates the actual math. Here the whole pipeline — Eq. 10
selection (with the per-cloud quota and tie-break noise), vmapped local
training over a fixed-size selected set, update-level attacks, per-link
compression with error-feedback residuals carried in state, hierarchical
aggregation, and byte/cost accounting — lives inside one jitted program,
so a T-round simulation is ONE device call and an S-seed sweep is one
vmapped device call.

Design rules that keep everything jit/scan/vmap-compatible:

* every shape is static: the selected set always has
  :func:`repro.core.selection.selected_count` rows (dropout masks rows
  instead of shrinking them);
* environment scenarios enter as *data* (``scenarios.JitHooks``): a
  dropout probability, an active-malice warmup round, a per-round
  ``c_cross`` multiplier schedule indexed by ``t``;
* all round randomness derives from ``PRNGKey(seed·7919 + t)`` — the
  same key schedule as the host loop, so a resumed/re-driven round
  replays bit-identically (the product is computed in int32 on device,
  so seeds ≥ ~271k wrap mod 2³² — still fully deterministic, just no
  longer the literal formula);
* compiled engines are cached per :class:`EngineStatic`, so the dozens
  of servers a scenario × method matrix instantiates share executables.

``FLServer`` is a thin stateful wrapper over :func:`compiled`;
``run_simulation_batch`` drives the vmapped path. Scenarios with host
hooks but no ``jit_hooks`` fall back to the legacy host loop.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.compress import build_link_policy, ef_step_masked
from repro.configs.base import FLConfig
from repro.core import (CloudTopology, CostModel, ReputationState,
                        apply_update_attack, coordinate_median, fedavg,
                        fltrust, krum, trimmed_mean)
from repro.core.attacks import UPDATE_ATTACKS
from repro.core import features as feats_mod
from repro.core.shapley import gradient_contribution
from repro.core.trust import cloud_trust
from repro.core.cost import hierarchical_unit_costs_jax, round_bytes_jax
from repro.core.selection import (exploration_quota,
                                  select_clients_jax, selected_count)
from repro.data.pipeline import FederatedData
from repro.federated import client as client_mod
from repro.scenarios.base import JitHooks, Scenario
from repro.telemetry import taps as taps_mod
from repro.telemetry.taps import TapSpec

Array = jax.Array

# Profiles name the round's device ops by the jax.named_scope phases
# below, which live only in HLO metadata. JAX's persistent compile cache
# leaves metadata out of its key by default, so an executable compiled
# from another build of this program would be served with that build's
# scope names; keying on metadata keeps a profile's names the program's.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

_GB = 1024.0 ** 3
REF_BATCH = 32          # reference LocalTrain batch (client default)

# key-fold tags for the per-round sub-streams. 0–3 and 211/223 are the
# compression folds inherited from the host loop; selection and dropout
# are engine-only streams (the host path draws those from numpy).
_FOLD_SELECT = 131
_FOLD_DROPOUT = 137
_FOLD_CLIENT_WIRE = 211
_FOLD_EDGE_WIRE = 223

# aggregators whose math is a 0-weighted sum over masked rows, i.e. safe
# when dropout zeroes non-delivered rows of the fixed-size update matrix.
# Order statistics (krum / trimmed_mean / median) would see the zero rows
# as extra clients — those fall back to the host loop under dropout.
MASKED_DELIVERY_OK = ("cost_trustfl", "fedavg", "fltrust")

METHODS = ("cost_trustfl", "fedavg", "krum", "trimmed_mean", "median",
           "fltrust")


# ---------------------------------------------------------------------------
# pytrees

class RoundState(NamedTuple):
    """Everything a round mutates, as one device-resident pytree
    (vmappable over a leading seeds axis)."""
    params: Dict[str, Array]     # model parameters
    rep_ema: Array               # (N,) Eq. 9 reputation EMA
    res_client: Array            # (N, D) EF residuals, client uplinks ((0,) when inactive)
    res_edge: Array              # (K, D) EF residuals, edge uplinks ((0,) when inactive)
    cum_cost: Array              # () running $ (float32; host reduces f64)
    cum_intra_bytes: Array       # () running intra-class wire bytes
    cum_cross_bytes: Array       # () running cross-cloud wire bytes
    feat_sep: Array              # (F,) per-feature separability EMA
                                 # (trust_features="multi"; (0,) otherwise)
    seed: Array                  # () int32 PRNG root: round key = PRNGKey(seed·7919+t)


class RoundOut(NamedTuple):
    """Per-round metrics emitted by ``round_step`` (stacked to (T, ...)
    by the scan driver)."""
    delivered: Array             # (N,) bool — selected AND delivered
    rep: Array                   # (N,) post-update reputation EMA
    cost: Array                  # () $ this round (float32 mirror)
    intra_bytes: Array           # () wire bytes, intra-class
    cross_bytes: Array           # () wire bytes, cross-cloud
    params_l2: Array             # () L2 of the post-update params — the
                                 # RoundState digest telemetry fingerprints
    feat_weights: Array          # (F,) adaptive feature mixing weights
                                 # (trust_features="multi"; (0,) otherwise)


class ClientData(NamedTuple):
    """Per-seed, round-invariant device inputs."""
    client_x: Array              # (N, S, ...) per-client samples
    client_y: Array              # (N, S) labels (already poisoned)
    ref_x: Array                 # (K, R, ...) per-cloud reference sets
    ref_y: Array                 # (K, R)
    malicious: Array             # (N,) bool static adversary set


class LastLayerSpec(NamedTuple):
    """The paper's g^(L) slice, derived from the params template: the
    last two leaves by insertion order (weight + bias of the final FC
    layer for the CNN — but any model's tail, not a hardcoded name)."""
    names: Tuple[str, ...]       # leaf names, template insertion order
    flat_idx: np.ndarray         # their positions in the raveled vector


@dataclass(frozen=True)
class EngineStatic:
    """Hashable round-engine configuration — the ``lru_cache`` key for
    :func:`compiled`, so equal configs share one set of executables."""
    method: str
    cloud_of: Tuple[int, ...]
    n_clouds: int
    aggregator_cloud: int
    input_shape: Tuple[int, ...]
    n_classes: int
    clients_per_round: int
    cost_lambda: float
    c_intra: float
    c_cross: float
    attack: str
    attack_scale: float
    gaussian_sigma: float
    attack_z: float
    local_epochs: int
    local_batch: int
    lr: float
    server_lr: float
    ema_gamma: float
    malicious_frac: float
    compressor: str
    compress_ratio: float
    qsgd_levels: int
    link_policy: str
    p_drop: float
    malice_warmup: int
    price_multipliers: Tuple[float, ...]
    trust_features: str = "scalar"

    @property
    def hierarchical(self) -> bool:
        return self.method == "cost_trustfl"

    @property
    def multi_features(self) -> bool:
        """Multi-feature trust gating is a cost_trustfl refinement — the
        flat baselines have no Eq. 7 path for it to gate."""
        return self.hierarchical and self.trust_features == "multi"

    @property
    def n_clients(self) -> int:
        return len(self.cloud_of)

    def topology(self) -> CloudTopology:
        return CloudTopology(cloud_of=np.array(self.cloud_of),
                             n_clouds=self.n_clouds,
                             aggregator_cloud=self.aggregator_cloud)


# ---------------------------------------------------------------------------
# flat-vector plumbing

def tree_l2(tree) -> Array:
    """L2 norm over every leaf of a pytree (float32 scalar) — the cheap
    in-graph state digest both device engines emit per round (and the
    host loop mirrors via one tiny jitted reduce)."""
    return jnp.sqrt(sum(jnp.sum(jnp.square(l))
                        for l in jax.tree.leaves(tree)))


def ravel_rows(tree) -> Array:
    """Flatten a pytree with leading batch axis into (B, D), in
    ``ravel_pytree`` leaf order — one concat, no per-row unravel."""
    leaves = jax.tree.leaves(tree)
    b = leaves[0].shape[0]
    return jnp.concatenate([l.reshape(b, -1) for l in leaves], axis=1)


def unflatten_like(vec: Array, template) -> Any:
    """Inverse of a single-row :func:`ravel_rows`: split a (D,) vector
    back into the template's pytree (static slice bounds)."""
    leaves, treedef = jax.tree.flatten(template)
    out, off = [], 0
    for l in leaves:
        n = int(np.prod(l.shape)) if l.ndim else 1
        out.append(vec[off:off + n].reshape(l.shape))
        off += n
    return jax.tree.unflatten(treedef, out)


def last_layer_spec(params_template: Dict[str, Array]) -> LastLayerSpec:
    """Derive the trust path's last-layer slice from the template: the
    last two leaves by insertion order (for non-dict templates, the last
    two of ``jax.tree.leaves``), plus their static positions in the
    raveled vector so flat matrices can be sliced directly."""
    if isinstance(params_template, dict):
        names = tuple(list(params_template)[-2:])
        chosen = [params_template[n] for n in names]
    else:  # generic pytree: best effort over the leaf tail
        leaves = jax.tree.leaves(params_template)
        names = tuple(str(i) for i in range(len(leaves))[-2:])
        chosen = leaves[-2:]
    # ravel_pytree order == jax.tree.leaves order (dicts: sorted keys)
    leaves, _ = jax.tree.flatten(params_template)
    offsets, off = [], 0
    for l in leaves:
        offsets.append(off)
        off += int(np.prod(l.shape)) if l.ndim else 1
    pos = {id(l): o for l, o in zip(leaves, offsets)}
    idx = np.concatenate([
        np.arange(pos[id(c)], pos[id(c)] + int(np.prod(c.shape)))
        for c in chosen])
    return LastLayerSpec(names=names, flat_idx=idx)


# ---------------------------------------------------------------------------
# shared round primitives (scan engine + sharded engine)
#
# Selection and delivery are REPLICATED computations in the sharded
# engine (every shard evaluates them on the full (N,) reputation/key),
# so both engines must build them from the same closures — a fork here
# would silently break cross-engine parity the first time one side's
# draw order changed.

def round_key(seed, t) -> Array:
    """The engine key schedule: ``PRNGKey(seed·7919 + t)`` (int32 on
    device — same wrap-around caveat as the module docstring)."""
    return jax.random.PRNGKey(seed * 7919 + t)


def build_select_fn(st: "EngineStatic") -> Tuple[Callable, int]:
    """``(select(rep, c_cross_t, key) -> (N,) bool mask, m_total)`` for
    this config: jittable Eq. 10 with the per-cloud quota + tie-break
    noise for cost_trustfl, a uniform draw for the baselines."""
    topo = st.topology()
    n = topo.n_clients
    cloud_of_np = np.array(st.cloud_of)
    cloud_sizes = np.bincount(cloud_of_np, minlength=st.n_clouds)
    cloud_of_j = jnp.asarray(cloud_of_np)
    quota = exploration_quota(st.cost_lambda) if st.hierarchical else 0
    m_total = selected_count(n, st.clients_per_round, quota, cloud_of_np)

    def select(rep: Array, c_cross_t, key: Array) -> Array:
        if st.hierarchical:
            unit_costs = hierarchical_unit_costs_jax(
                cloud_of_j, cloud_sizes, st.aggregator_cloud, st.c_intra,
                c_cross_t)
            return select_clients_jax(
                rep, unit_costs, st.clients_per_round, st.cost_lambda,
                per_cloud_min=quota, cloud_of=cloud_of_np, key=key)
        perm = jax.random.permutation(key, n)
        return jnp.zeros((n,), bool).at[perm[:m_total]].set(True)

    return select, m_total


def selected_total(st: "EngineStatic") -> int:
    """Static population of the selected set for this config — the
    ``n_selected`` every telemetry round event reports (see
    ``core.selection.selected_count``)."""
    quota = exploration_quota(st.cost_lambda) if st.hierarchical else 0
    return selected_count(st.n_clients, st.clients_per_round, quota,
                          np.array(st.cloud_of))


def build_deliver_fn(st: "EngineStatic") -> Callable:
    """``deliver(sel, key) -> (N,) bool`` dropout mask (identity when the
    scenario declares no ``p_drop``; never drops the whole round)."""
    n = st.n_clients

    def deliver(sel: Array, key: Array) -> Array:
        if st.p_drop <= 0.0:
            return sel
        out = sel & (jax.random.uniform(key, (n,)) >= st.p_drop)
        # never drop everyone: re-admit the first selected client
        need = sel.any() & ~out.any()
        return out | (need & (jnp.arange(n) == jnp.argmax(sel)) & sel)

    return deliver


def build_edge_wire_fn(lp, k: int, aggregator_cloud: int) -> Callable:
    """``edge_wire(cloud_aggs, res_edge, active, ekey) -> (cloud_aggs,
    res_edge)``: the edge→global wire model shared by every driver (scan
    engine, sharded engine, and the host loop's ``cloud_transform``) —
    round-trips the (K, D) cloud aggregates through each cloud's uplink
    codec (intra-class for the aggregator's own cloud, cross for the
    rest) with error feedback on the edge residuals.

    ``active`` is a (K, 1) mask of clouds with ≥1 delivered client:
    inactive clouds pass through and keep their residual — their row is
    the receiver-side reference fallback, nothing crossed the wire.
    ``ekey`` is the ``_FOLD_EDGE_WIRE`` stream; the 2=intra / 3=cross
    sub-folds are part of the cross-engine parity contract — change
    them here or nowhere."""
    def edge_wire(cloud_aggs: Array, res_edge: Array, active: Array,
                  ekey: Array) -> Tuple[Array, Array]:
        is_agg = (jnp.arange(k) == aggregator_cloud)[:, None]
        y = cloud_aggs + res_edge
        hat_cross = lp.cross.roundtrip(y, jax.random.fold_in(ekey, 3))
        # identity roundtrips are free; "all" shares one codec object,
        # so don't run it twice over the same rows
        hat_intra = (hat_cross if lp.intra is lp.cross
                     else lp.intra.roundtrip(y, jax.random.fold_in(ekey, 2)))
        x_hat = jnp.where(is_agg, hat_intra, hat_cross)
        return (jnp.where(active, x_hat, cloud_aggs),
                jnp.where(active, y - x_hat, res_edge))

    return edge_wire


def init_round_state(st: "EngineStatic", d: int, seed: int, *,
                     client_wire_active: bool,
                     edge_wire_active: bool) -> RoundState:
    """The round-zero :class:`RoundState` shared by the scan and sharded
    engines (the sharded engine adds mesh placement on top): per-seed
    model init, uniform reputation, EF residual buffers only for the
    link classes whose codecs actually distort the wire."""
    n, k = st.n_clients, st.n_clouds
    params = client_mod.cnn_init(jax.random.PRNGKey(seed), st.input_shape,
                                 st.n_classes)
    return RoundState(
        params=params,
        rep_ema=ReputationState.init(n).ema,
        res_client=(jnp.zeros((n, d), jnp.float32)
                    if client_wire_active else jnp.zeros((0,))),
        res_edge=(jnp.zeros((k, d), jnp.float32)
                  if edge_wire_active else jnp.zeros((0,))),
        cum_cost=jnp.float32(0.0), cum_intra_bytes=jnp.float32(0.0),
        cum_cross_bytes=jnp.float32(0.0),
        feat_sep=(jnp.zeros((feats_mod.N_FEATURES,), jnp.float32)
                  if st.multi_features else jnp.zeros((0,))),
        seed=jnp.int32(seed))


def host_round_accounting(static: "EngineStatic", d_params: int,
                          client_payload: np.ndarray,
                          edge_payload: np.ndarray,
                          delivered_rounds: np.ndarray,
                          t0: int = 0) -> np.ndarray:
    """Byte-exact float64 (cost, intra_bytes, cross_bytes) rows for a
    (T, N) stack of delivered masks — the single accounting code path
    shared by every engine driver (per-round ``FLServer``, the
    ``lax.scan`` batch, and the sharded mesh engine), so all of them
    bill identically at any scale, immune to the float32 in-state
    mirrors' 2^24 exactness bound."""
    st = static
    topo = st.topology()
    mults = st.price_multipliers
    rows = np.empty((len(delivered_rounds), 3), np.float64)
    for i, dmask in enumerate(np.asarray(delivered_rounds, bool)):
        cm = CostModel(st.c_intra,
                       st.c_cross * mults[(t0 + i) % len(mults)])
        intra_b, cross_b = cm.round_bytes(
            topo, dmask, d_params, hierarchical=st.hierarchical,
            client_payload=client_payload, edge_payload=edge_payload)
        cost = cm.round_cost(
            topo, dmask, d_params, hierarchical=st.hierarchical,
            client_payload=client_payload, edge_payload=edge_payload)
        rows[i] = (cost, intra_b, cross_b)
    return rows


# ---------------------------------------------------------------------------
# context construction

def hooks_of(scenario: Optional[Scenario]) -> JitHooks:
    if scenario is None or scenario.jit_hooks is None:
        return JitHooks()
    return scenario.jit_hooks


def supports(flcfg: FLConfig, method: str,
             scenario: Optional[Scenario] = None) -> bool:
    """Can the device engine run this (config, method, scenario)?"""
    if method not in METHODS or flcfg.attack not in UPDATE_ATTACKS:
        return False
    if scenario is not None and not scenario.jittable:
        return False
    if hooks_of(scenario).p_drop > 0 and method not in MASKED_DELIVERY_OK:
        return False
    return True


def resolve_engine(engine: str, flcfg: FLConfig, topo: CloudTopology,
                   method: str, scenario: Optional[Scenario] = None, *,
                   n_devices: Optional[int] = None) -> str:
    """Route a (config, method, scenario) onto a round driver:
    ``"shard"`` (mesh-sharded engine), ``"jit"`` (single-device scan
    engine) or ``"host"`` (legacy loop).

    ``engine="auto"`` prefers the sharded engine when more than one
    device is visible AND the combination is shard-supported, then the
    scan engine, then the host loop — which stays the only driver for
    host-hook scenarios and for dropout with order-statistic
    aggregators. Forcing ``"jit"``/``"shard"`` on an unsupported
    combination raises with the reason (loud failure, never a silent
    mis-aggregation)."""
    from repro.federated import sharded as sharded_mod
    if n_devices is None:
        n_devices = len(jax.devices())
    if engine == "host":
        return "host"
    if engine == "shard":
        reason = sharded_mod.shard_unsupported_reason(
            flcfg, topo, method, scenario, n_devices=n_devices)
        if reason is not None:
            raise ValueError(f"engine='shard' but {reason}")
        return "shard"
    if engine == "jit":
        if not supports(flcfg, method, scenario):
            raise ValueError(
                f"engine='jit' but method={method!r} / "
                f"scenario={getattr(scenario, 'name', None)!r} "
                "is not jittable")
        return "jit"
    if engine != "auto":
        raise ValueError(f"unknown engine {engine!r}; expected "
                         "'auto' | 'shard' | 'jit' | 'host'")
    # the sharded engine trains ALL clients with masking (fixed per-shard
    # shapes), so auto only prefers it at dense participation, where the
    # masked rows are not wasted work; forcing engine="shard" skips this
    # heuristic
    dense = 2 * flcfg.clients_per_round >= topo.n_clients
    if (n_devices > 1 and dense and sharded_mod.shard_unsupported_reason(
            flcfg, topo, method, scenario, n_devices=n_devices) is None):
        return "shard"
    if supports(flcfg, method, scenario):
        return "jit"
    return "host"


def static_from(flcfg: FLConfig, topo: CloudTopology, method: str,
                scenario: Optional[Scenario] = None,
                input_shape: Tuple[int, ...] = (32, 32, 3),
                n_classes: int = 10) -> EngineStatic:
    """Freeze the engine-relevant slice of (FLConfig, topology, scenario)
    into the hashable compile key."""
    if not supports(flcfg, method, scenario):
        raise ValueError(
            f"engine cannot run method={method!r} attack={flcfg.attack!r} "
            f"scenario={getattr(scenario, 'name', None)!r} (host-hook "
            "scenario, unknown method, or dropout with an order-statistic "
            "aggregator) — use the host loop")
    if flcfg.trust_features not in ("scalar", "multi"):
        raise ValueError(f"unknown trust_features {flcfg.trust_features!r}; "
                         "use 'scalar' or 'multi'")
    h = hooks_of(scenario)
    return EngineStatic(
        method=method, cloud_of=tuple(int(c) for c in topo.cloud_of),
        n_clouds=topo.n_clouds, aggregator_cloud=topo.aggregator_cloud,
        input_shape=tuple(input_shape), n_classes=int(n_classes),
        clients_per_round=flcfg.clients_per_round,
        cost_lambda=flcfg.cost_lambda, c_intra=flcfg.c_intra,
        c_cross=flcfg.c_cross, attack=flcfg.attack,
        attack_scale=flcfg.attack_scale, gaussian_sigma=flcfg.gaussian_sigma,
        attack_z=flcfg.attack_z, local_epochs=flcfg.local_epochs,
        local_batch=flcfg.local_batch, lr=flcfg.lr,
        server_lr=flcfg.server_lr, ema_gamma=flcfg.ema_gamma,
        malicious_frac=flcfg.malicious_frac, compressor=flcfg.compressor,
        compress_ratio=flcfg.compress_ratio, qsgd_levels=flcfg.qsgd_levels,
        link_policy=flcfg.link_policy, p_drop=float(h.p_drop),
        malice_warmup=int(h.malice_warmup),
        price_multipliers=tuple(float(m) for m in h.price_multipliers),
        trust_features=flcfg.trust_features)


def draw_malicious(flcfg: FLConfig, n_clients: int, seed: int) -> np.ndarray:
    """The host loop's static adversary draw (shared so engine and
    legacy paths agree on who is malicious for a given seed)."""
    rng = np.random.default_rng(seed)
    n_mal = int(flcfg.malicious_frac * n_clients)
    mal = np.zeros(n_clients, bool)
    mal[rng.choice(n_clients, n_mal, replace=False)] = True
    return mal


def poison_labels(flcfg: FLConfig, data: FederatedData,
                  malicious: np.ndarray, seed: int) -> np.ndarray:
    """The host loop's label_flip poisoning (identity otherwise)."""
    y = np.array(data.client_y)
    if flcfg.attack != "label_flip":
        return y
    rng = np.random.default_rng(seed + 1)
    nc = data.n_classes
    for i in np.nonzero(malicious)[0]:
        y[i] = (y[i] + rng.integers(1, nc, size=y[i].shape)) % nc
    return y


def make_client_data(flcfg: FLConfig, topo: CloudTopology,
                     data: FederatedData, seed: int,
                     malicious: Optional[np.ndarray] = None,
                     poisoned_y: Optional[np.ndarray] = None) -> ClientData:
    """Stage one seed's round-invariant inputs on device."""
    if malicious is None:
        malicious = draw_malicious(flcfg, topo.n_clients, seed)
    if poisoned_y is None:
        poisoned_y = poison_labels(flcfg, data, malicious, seed)
    return ClientData(client_x=jnp.asarray(data.client_x),
                      client_y=jnp.asarray(poisoned_y),
                      ref_x=jnp.asarray(data.ref_x),
                      ref_y=jnp.asarray(data.ref_y),
                      malicious=jnp.asarray(malicious))


# ---------------------------------------------------------------------------
# the compiled engine

@dataclass(frozen=True)
class CompiledEngine:
    """Jitted drivers plus the host-side constants needed to account a
    run (payload vectors, price schedule, last-layer spec)."""
    static: EngineStatic
    step: Callable        # (state, data, t) -> (state, RoundOut)
    run: Callable         # (state, data, rounds) -> (state, RoundOut[T])
    run_batch: Callable   # (state[S], data[S], rounds) -> (state[S], RoundOut[S, T])
    # run_batch with client_x/ref_x/ref_y broadcast (one device copy)
    # and only the per-seed leaves (client_y, malicious) stacked
    run_batch_shared: Callable
    init_state: Callable  # (seed) -> RoundState
    d_params: int
    ll_spec: LastLayerSpec
    client_payload: np.ndarray   # (N,) exact bytes per client uplink
    edge_payload: np.ndarray     # (K,) exact bytes per edge uplink

    def host_round_accounting(self, delivered_rounds: np.ndarray,
                              t0: int = 0) -> np.ndarray:
        """See :func:`host_round_accounting` (module level — shared with
        the sharded engine)."""
        return host_round_accounting(self.static, self.d_params,
                                     self.client_payload, self.edge_payload,
                                     delivered_rounds, t0=t0)


def compiled(static: EngineStatic,
             tap: Optional[TapSpec] = None) -> CompiledEngine:
    """Build (once per (config, tap)) the pure ``round_step`` and its
    jitted step / scan / vmapped-scan drivers.

    ``tap`` — an optional ``repro.telemetry.taps.TapSpec``: when
    enabled, the ``step`` and ``run`` drivers stream ``(t, RoundOut)``
    to the host after every round via an ordered ``jax.debug.callback``
    (install a consumer with ``taps.collecting``); when ``None`` or
    disabled, the build is IDENTICAL to one that never heard of
    telemetry — a disabled tap normalizes to the untapped cache entry,
    so it is the SAME executable, zero added ops. Ordered callbacks
    cannot cross ``vmap``, so the multi-seed batch drivers always run
    untapped and telemetry replays their stacked outputs post-run."""
    if tap is not None and not tap.enabled:
        tap = None
    return _compiled(static, tap)


@lru_cache(maxsize=None)
def _compiled(static: EngineStatic,
              tap: Optional[TapSpec]) -> CompiledEngine:
    st = static
    topo = st.topology()
    n, k = topo.n_clients, topo.n_clouds
    agg = topo.aggregator_cloud
    cloud_of_np = np.array(st.cloud_of)
    cloud_of_j = jnp.asarray(cloud_of_np)
    hier = st.hierarchical

    # template params: shapes only (the real init is per-seed)
    template = client_mod.cnn_init(jax.random.PRNGKey(0), st.input_shape,
                                   st.n_classes)
    d = int(sum(int(np.prod(l.shape)) for l in jax.tree.leaves(template)))
    ll = last_layer_spec(template)
    ll_idx = jnp.asarray(ll.flat_idx)

    lp = build_link_policy(st.compressor, ratio=st.compress_ratio,
                           levels=st.qsgd_levels, link_policy=st.link_policy)
    client_payload, edge_payload = lp.payload_vectors(topo, d,
                                                      hierarchical=hier)
    client_wire_active = ((not lp.intra.is_identity) if hier
                          else lp.any_active)
    edge_wire_active = hier and lp.any_active

    # selection/delivery closures shared with the sharded engine; m_total
    # is resolved statically so the selected set has a fixed population
    # count under jit (see core.selection.exploration_quota)
    _select, m_total = build_select_fn(st)
    _deliver = build_deliver_fn(st)
    _edge_wire = build_edge_wire_fn(lp, k, agg)

    price_arr = jnp.asarray(st.price_multipliers, jnp.float32)
    n_mult = len(st.price_multipliers)
    cp_j = jnp.asarray(client_payload, jnp.float32)
    ep_j = jnp.asarray(edge_payload, jnp.float32)

    f_mal = int(st.malicious_frac * m_total)

    train_sel = jax.vmap(
        lambda p, x, y, kk: client_mod.local_train(
            p, x, y, kk, epochs=st.local_epochs, batch=st.local_batch,
            lr=st.lr),
        in_axes=(None, 0, 0, 0))
    # reference LocalTrain shares the clients' schedule (Eq. 12 rescale
    # preserves the effective server step size)
    train_ref = jax.vmap(
        lambda p, x, y, kk: client_mod.local_train(
            p, x, y, kk, epochs=st.local_epochs, batch=REF_BATCH, lr=st.lr),
        in_axes=(None, 0, 0, None))

    def round_step(state: RoundState, data: ClientData, t
                   ) -> Tuple[RoundState, RoundOut]:
        # phase scopes (jax.named_scope) label the emitted ops for
        # profiler traces / HLO metadata — they change nothing at runtime
        t = jnp.asarray(t, jnp.int32)
        key = round_key(state.seed, t)
        mult = price_arr[jnp.mod(t, n_mult)] if n_mult > 1 else price_arr[0]
        c_cross_t = st.c_cross * mult

        with jax.named_scope("round.select"):
            sel = _select(state.rep_ema, c_cross_t,
                          jax.random.fold_in(key, _FOLD_SELECT))
            delivered = _deliver(sel, jax.random.fold_in(key, _FOLD_DROPOUT))
            sel_idx = jnp.nonzero(sel, size=m_total, fill_value=0)[0]
            valid = delivered[sel_idx]                   # (m_total,) bool

        # local training over the fixed-size selected set (dropped
        # clients train too — fixed shapes — but are masked below)
        with jax.named_scope("round.train"):
            keys = jax.random.split(key, n)
            upd_tree = train_sel(state.params, data.client_x[sel_idx],
                                 data.client_y[sel_idx], keys[sel_idx])
            flat_sel = ravel_rows(upd_tree)              # (m_total, D)

        # update-level attacks on this round's ACTIVE malicious clients
        with jax.named_scope("round.attack"):
            mal = data.malicious
            if st.malice_warmup > 0:
                mal = mal & (t >= st.malice_warmup)
            mal_sel = mal[sel_idx] & valid
            flat_sel = apply_update_attack(
                st.attack, flat_sel, mal_sel, key, sigma=st.gaussian_sigma,
                scale=st.attack_scale, z=st.attack_z,
                valid=valid if st.p_drop > 0 else None)

        # client uplink wire (EF residuals gathered/scattered from state)
        res_client = state.res_client
        if client_wire_active:
            with jax.named_scope("round.compress"):
                ckey = jax.random.fold_in(key, _FOLD_CLIENT_WIRE)
                cur = res_client[sel_idx]
                if hier:   # every client→edge hop is intra-class
                    flat_sel, cur = ef_step_masked(lp.intra, flat_sel, cur,
                                                   valid, ckey, sel_idx)
                else:      # flat path: intra or cross by co-location
                    same = cloud_of_j[sel_idx] == agg
                    flat_sel, cur = ef_step_masked(
                        lp.intra, flat_sel, cur, valid & same,
                        jax.random.fold_in(ckey, 0), sel_idx)
                    flat_sel, cur = ef_step_masked(
                        lp.cross, flat_sel, cur, valid & ~same,
                        jax.random.fold_in(ckey, 1), sel_idx)
                res_client = res_client.at[sel_idx].set(cur)

        # trust statistics read the attacked+compressed wire view
        if st.p_drop > 0:
            flat_sel = jnp.where(valid[:, None], flat_sel, 0.0)
        ll_sel = flat_sel[:, ll_idx]

        res_edge = state.res_edge
        new_rep = state.rep_ema
        new_feat_sep = state.feat_sep
        feat_w = jnp.zeros((0,), jnp.float32)
        with jax.named_scope("round.aggregate"):
            if hier:
                # compact Eq. 5–13: the same pipeline as
                # core.cost_trustfl_aggregate, but over the (m_total, D)
                # selected rows instead of a zero-padded (N, D) scatter —
                # aggregation traffic scales with the round's participants,
                # not the fleet (N/m× less memory movement, and the vmapped
                # multi-seed batch stays cache-resident)
                eps = 1e-12
                f32 = flat_sel.dtype
                with jax.named_scope("ref_train"):
                    ref_tree = train_ref(state.params, data.ref_x,
                                         data.ref_y, key)
                    ref_flat = ravel_rows(ref_tree)
                ref_ll = ref_flat[:, ll_idx]
                sel_cloud = cloud_of_j[sel_idx]                   # (m,)
                onehot = jax.nn.one_hot(sel_cloud, k, dtype=f32)  # (m, K)
                w = valid.astype(f32)
                ref_ll_sel = ref_ll[sel_cloud]                    # (m, L)

                # Eq. 7 with the median-damped norm factor (see core)
                gbar = (w @ ll_sel) / jnp.maximum(jnp.sum(w), 1.0)
                norms = jnp.linalg.norm(ll_sel, axis=1)
                med = jnp.nanmedian(jnp.where(w > 0, norms, jnp.nan))
                damp = jnp.minimum(1.0,
                                   (med / jnp.maximum(norms, eps)) ** 2)
                damp = jnp.where(jnp.isnan(damp), 1.0, damp)
                phi = gradient_contribution(ll_sel, gbar) * damp * w

                # multi-feature gate (core.features): phi scaled by the
                # adaptively-weighted feature vector of each delivered
                # row; separability labels come from the PREVIOUS
                # reputation EMA (pre-Eq. 8–9 update)
                if st.multi_features:
                    feats = feats_mod.client_features(
                        ll_sel, ref_ll_sel, gbar, med, w, eps)
                    sep_round = feats_mod.separability(feats, w, eps)
                    new_feat_sep = (
                        feats_mod.FEAT_SEP_RHO * state.feat_sep
                        + (1.0 - feats_mod.FEAT_SEP_RHO) * sep_round)
                    feat_w = feats_mod.feature_weights(new_feat_sep)
                    phi = phi * feats_mod.gate(feats, new_feat_sep)

                # Eq. 8–9: normalize over the round (non-selected φ are
                # 0), EMA only for delivered participants
                total = jnp.sum(phi)
                r = jnp.where(total > eps, phi / jnp.maximum(total, eps),
                              1.0 / n)
                rep_sel = (st.ema_gamma * state.rep_ema[sel_idx]
                           + (1.0 - st.ema_gamma) * r)
                rep_sel = jnp.where(valid, rep_sel, state.rep_ema[sel_idx])
                new_rep = state.rep_ema.at[sel_idx].set(rep_sel)

                # Eq. 11: trust vs. the client's own cloud reference
                dots = jnp.sum(ll_sel * ref_ll_sel, axis=1)
                cos = dots / jnp.maximum(
                    norms * jnp.linalg.norm(ref_ll_sel, axis=1), eps)
                ts = jax.nn.relu(cos) * rep_sel * w

                # Eq. 12: rescale to own-cloud reference norm
                ref_norms = jnp.linalg.norm(ref_flat, axis=1)     # (K,)
                g_tilde = flat_sel * (ref_norms[sel_cloud] / jnp.maximum(
                    jnp.linalg.norm(flat_sel, axis=1), eps))[:, None]

                # Eq. 13 per cloud (intra-cloud phase, Eq. 5)
                ts_cloud = onehot.T @ ts                          # (K,)
                cloud_aggs = (onehot.T @ (g_tilde * ts[:, None])
                              / jnp.maximum(ts_cloud, eps)[:, None])
                if edge_wire_active:
                    active = (onehot.T @ w > 0)[:, None]
                    with jax.named_scope("edge_codec"):
                        cloud_aggs, res_edge = _edge_wire(
                            cloud_aggs, res_edge, active,
                            jax.random.fold_in(key, _FOLD_EDGE_WIRE))
                # empty/zero-trust clouds fall back to their reference
                cloud_aggs = jnp.where((ts_cloud > eps)[:, None],
                                       cloud_aggs, ref_flat)

                # Eq. 6: cross-cloud phase, β_k from the global reference
                beta = cloud_trust(cloud_aggs, jnp.mean(ref_flat, axis=0))
                update = beta @ cloud_aggs
            else:
                u = flat_sel
                if st.method == "fedavg":
                    if st.p_drop > 0:
                        w = valid.astype(u.dtype)
                        update = (w @ u) / jnp.maximum(jnp.sum(w), 1.0)
                    else:
                        update = fedavg(u)
                elif st.method == "krum":
                    update = krum(u, f_mal,
                                  multi=max(1, m_total - f_mal - 2))
                elif st.method == "trimmed_mean":
                    update = trimmed_mean(u,
                                          trim_frac=st.malicious_frac / 2)
                elif st.method == "median":
                    update = coordinate_median(u)
                else:  # fltrust — zero (dropped) rows get ts=0, so it's
                       # already masked-delivery safe
                    with jax.named_scope("ref_train"):
                        ref_tree = train_ref(state.params, data.ref_x,
                                             data.ref_y, key)
                        ref_flat = ravel_rows(ref_tree)
                    update = fltrust(u, jnp.mean(ref_flat, axis=0))

            # apply: w <- w - eta * g  (g is a model delta)
            delta = unflatten_like(update * st.server_lr, state.params)
            params = jax.tree.map(lambda w, g: w - g, state.params, delta)

        with jax.named_scope("round.account"):
            # byte-exact wire accounting (float32 in-graph mirror; the
            # host drivers re-derive float64 totals from `delivered`)
            intra_b, cross_b = round_bytes_jax(delivered, cloud_of_j, agg,
                                               cp_j, ep_j,
                                               hierarchical=hier)
            cost = (intra_b * st.c_intra + cross_b * c_cross_t) / _GB
            digest = tree_l2(params)

        new_state = RoundState(
            params=params, rep_ema=new_rep, res_client=res_client,
            res_edge=res_edge, cum_cost=state.cum_cost + cost,
            cum_intra_bytes=state.cum_intra_bytes + intra_b,
            cum_cross_bytes=state.cum_cross_bytes + cross_b,
            feat_sep=new_feat_sep, seed=state.seed)
        out = RoundOut(delivered=delivered, rep=new_rep, cost=cost,
                       intra_bytes=intra_b, cross_bytes=cross_b,
                       params_l2=digest, feat_weights=feat_w)
        return new_state, out

    # the tapped step feeds ONLY the unbatched drivers; when the tap is
    # off/absent this is round_step itself and nothing changes
    tapped_step = taps_mod.instrument(round_step, tap)

    step = jax.jit(tapped_step)

    def _scan(state, data, ts):
        return jax.lax.scan(lambda c, t: tapped_step(c, data, t), state, ts)

    def _scan_untapped(state, data, ts):
        return jax.lax.scan(lambda c, t: round_step(c, data, t), state, ts)

    scan_jit = jax.jit(_scan)
    # batch drivers vmap the UNTAPPED scan (ordered callbacks cannot
    # cross vmap; multi-seed events are replayed post-run instead)
    scan_batch_jit = jax.jit(jax.vmap(_scan_untapped, in_axes=(0, 0, None)))
    # seeds sharing one dataset: broadcast the sample arrays instead of
    # stacking S copies (labels and the adversary draw stay per-seed)
    _shared_axes = ClientData(client_x=None, client_y=0, ref_x=None,
                              ref_y=None, malicious=0)
    scan_batch_shared_jit = jax.jit(
        jax.vmap(_scan_untapped, in_axes=(0, _shared_axes, None)))

    def run(state: RoundState, data: ClientData, rounds: int):
        """lax.scan the engine over ``rounds`` rounds — one device call."""
        return scan_jit(state, data, jnp.arange(rounds, dtype=jnp.int32))

    def run_batch(states: RoundState, datas: ClientData, rounds: int):
        """vmap(run): stacked states/datas with a leading seeds axis."""
        return scan_batch_jit(states, datas,
                              jnp.arange(rounds, dtype=jnp.int32))

    def run_batch_shared(states: RoundState, data: ClientData, rounds: int):
        """vmap(run) over seeds sharing one dataset: ``data`` carries
        unstacked (N, ...) sample/reference arrays and stacked (S, ...)
        labels + malicious masks."""
        return scan_batch_shared_jit(states, data,
                                     jnp.arange(rounds, dtype=jnp.int32))

    def init_state(seed: int) -> RoundState:
        return init_round_state(st, d, seed,
                                client_wire_active=client_wire_active,
                                edge_wire_active=edge_wire_active)

    return CompiledEngine(static=st, step=step, run=run,
                          run_batch=run_batch,
                          run_batch_shared=run_batch_shared,
                          init_state=init_state,
                          d_params=d, ll_spec=ll,
                          client_payload=client_payload,
                          edge_payload=edge_payload)
