"""Mesh-sharded round engine: the client population laid out over a
``("cloud", "client")`` device mesh via ``shard_map``, with Eq. 5–13
hierarchical aggregation realized as a two-stage reduction — intra-cloud
``psum`` over the ``client`` axis, then a cross-cloud combine over the
``cloud`` axis — mirroring the production train step's ``two_phase``
strategy (``repro.train.steps``).

This is the physical realization of the paper's topology: clouds map to
mesh columns (cheap intra-column reductions = intra-cloud traffic),
the cross-column combine is the single per-cloud egress hop. Each shard
owns a contiguous block of clients and keeps their training data and
error-feedback residuals resident; per round it

* evaluates Eq. 10 selection + delivery REPLICATED on the full (N,)
  reputation (tiny, and bit-identical to the single-device engine —
  the closures are shared, see ``engine.build_select_fn``);
* trains ALL of its local clients with fixed shapes and masks the
  non-selected rows out of every statistic ("masked local training"):
  under jit the selected subset has no static per-shard size, so the
  sharded engine's sweet spot is dense participation (fleet sweeps,
  ``clients_per_round`` ≈ N) — at sparse participation the single-
  device engine trains fewer rows and ``engine="auto"`` prefers it;
* applies update attacks and per-link compression per shard (honest-
  statistics adversaries get their moments from masked global
  reductions over the same row set the single-device engine sees);
* aggregates hierarchically in two stages and accounts bytes/$ from the
  replicated delivered mask — the SAME ``round_bytes_jax`` reduction as
  the scan engine, so cost accounting stays byte-exact: intra-column
  reductions are billed at ``c_intra``, the cross-column combine at the
  (possibly scheduled) ``c_cross``.

Support surface (``shard_unsupported_reason``): all six methods run, but
configurations whose randomness or statistics are *matrix-shaped* are
rejected with a clear error instead of silently mis-aggregating —
``gaussian`` draws an (m, D) noise tensor and ``min_max`` bisects on the
pairwise Gram of the selected matrix; their values depend on row
position in the selected matrix, which no longer exists as one array.
(``qsgd`` used to be in this list, but its rounding noise is now keyed
per SENDER — ``fold_in(client_id)`` — so it shards exactly; see
``repro.compress.qsgd``.) Order-statistic aggregators (krum /
trimmed_mean / median) ARE supported: the (m_total, D) selected matrix is
re-materialized replicated via a slot-scatter psum (rows land in the
exact ``sel_idx`` order of the scan engine), which costs one m×D
all-reduce — acceptable because m ≪ N is the only regime those baselines
run at.

Parity contract (tests/test_sharded.py): on a 1×1 mesh the sharded
engine matches the single-device scan engine to documented fp tolerance
(selection masks, delivered masks and byte/cost accounting exactly;
params/reputation to ~1e-4 relative, the bound the tests enforce —
psum partial sums associate differently than one flat matmul, so
bitwise equality is not promised).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import lru_cache
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compress import build_link_policy, ef_step_masked
from repro.configs.base import FLConfig
from repro.core import CloudTopology
from repro.core import features as feats_mod
from repro.core.cost import round_bytes_jax
from repro.core.robust import coordinate_median, krum, trimmed_mean
from repro.core.shapley import gradient_contribution
from repro.core.trust import cloud_trust
from repro.data.pipeline import FederatedData
from repro.federated import client as client_mod
from repro.federated import engine as engine_mod
from repro.federated.engine import (ClientData, EngineStatic, LastLayerSpec,
                                    MASKED_DELIVERY_OK, METHODS, REF_BATCH,
                                    RoundOut, RoundState, _FOLD_CLIENT_WIRE,
                                    _FOLD_DROPOUT, _FOLD_EDGE_WIRE,
                                    _FOLD_SELECT, build_deliver_fn,
                                    build_edge_wire_fn, build_select_fn,
                                    hooks_of, host_round_accounting,
                                    init_round_state, last_layer_spec,
                                    ravel_rows, round_key, tree_l2,
                                    unflatten_like)
from repro.scenarios.base import Scenario

Array = jax.Array

_GB = 1024.0 ** 3
AXES = ("cloud", "client")

# attacks whose per-round transform decomposes over client shards: either
# per-row (sign_flip / scaling / the data-level label_flip) or driven by
# masked GLOBAL moments that psum/all_gather cleanly (alie / alie_norm /
# ipm / collusion). ``gaussian`` (an (m, D) noise tensor) and ``min_max``
# (bisection on the selected matrix's pairwise Gram) are matrix-shaped —
# scan engine only.
SHARD_ATTACKS = ("none", "label_flip", "sign_flip", "scaling", "alie",
                 "alie_norm", "ipm", "collusion")

# ``topk`` is per-row deterministic and ``qsgd`` keys its rounding noise
# per sender (fold_in(client_id), see repro.compress.qsgd) — both shard
# exactly.
SHARD_COMPRESSORS = ("none", "topk", "qsgd")


# ---------------------------------------------------------------------------
# mesh construction / support gating

def mesh_axes(n_clouds: int, n_clients: int,
              n_devices: Optional[int] = None) -> Optional[Tuple[int, int]]:
    """Factor the device count into ``(cloud, client)`` axis sizes:
    the cloud axis takes the largest common divisor so mesh columns own
    whole clouds (intra-cloud psums never cross columns). ``None`` when
    the population does not tile the devices."""
    if n_devices is None:
        n_devices = len(jax.devices())
    if n_devices < 1 or n_clients % n_devices != 0:
        return None
    kc = math.gcd(n_devices, n_clouds)
    return kc, n_devices // kc


def client_mesh(n_clouds: int, n_clients: int,
                n_devices: Optional[int] = None) -> Mesh:
    """``("cloud", "client")`` mesh over the visible devices."""
    if n_devices is None:
        n_devices = len(jax.devices())
    ax = mesh_axes(n_clouds, n_clients, n_devices)
    if ax is None:
        raise ValueError(
            f"cannot tile {n_clients} clients over {n_devices} devices")
    return jax.make_mesh(ax, AXES)


def _even_contiguous(topo: CloudTopology) -> bool:
    """The sharded layout requires the even contiguous client→cloud map
    (``CloudTopology.even``): cloud k owns clients [k·n_k, (k+1)·n_k)."""
    n, k = topo.n_clients, topo.n_clouds
    if n % k != 0:
        return False
    return bool(np.array_equal(topo.cloud_of,
                               np.arange(n) // (n // k)))


def shard_unsupported_reason(flcfg: FLConfig, topo: CloudTopology,
                             method: str,
                             scenario: Optional[Scenario] = None, *,
                             n_devices: Optional[int] = None
                             ) -> Optional[str]:
    """``None`` when the sharded engine can run this combination, else a
    human-readable reason (used verbatim in the raised error — the
    engine must refuse loudly, never silently mis-aggregate)."""
    if method not in METHODS:
        return f"unknown method {method!r}"
    if scenario is not None and not scenario.jittable:
        return (f"scenario {scenario.name!r} has host-only hooks "
                "(no JitHooks declaration)")
    if hooks_of(scenario).p_drop > 0 and method not in MASKED_DELIVERY_OK:
        return (f"dropout with order-statistic aggregator {method!r} "
                "(zero rows would count as clients)")
    if flcfg.attack not in SHARD_ATTACKS:
        return (f"attack {flcfg.attack!r} is matrix-shaped (randomness or "
                "statistics tied to the selected matrix's layout) — use "
                "the scan engine")
    if flcfg.compressor not in SHARD_COMPRESSORS:
        return (f"compressor {flcfg.compressor!r} is not "
                "shard-decomposable — use the scan engine")
    if not _even_contiguous(topo):
        return ("client→cloud layout is not the even contiguous "
                "CloudTopology.even map")
    if n_devices is None:
        n_devices = len(jax.devices())
    if mesh_axes(topo.n_clouds, topo.n_clients, n_devices) is None:
        return (f"{topo.n_clients} clients do not tile "
                f"{n_devices} devices")
    return None


def supports_shard(flcfg: FLConfig, method: str,
                   scenario: Optional[Scenario] = None, *,
                   topo: Optional[CloudTopology] = None,
                   n_devices: Optional[int] = None) -> bool:
    if topo is None:
        topo = CloudTopology.even(flcfg.n_clouds, flcfg.clients_per_cloud)
    return shard_unsupported_reason(flcfg, topo, method, scenario,
                                    n_devices=n_devices) is None


@dataclass(frozen=True)
class ShardStatic:
    """Compile key: the engine static plus the mesh factorization."""
    static: EngineStatic
    kc: int
    pc: int


def static_from_shard(flcfg: FLConfig, topo: CloudTopology, method: str,
                      scenario: Optional[Scenario] = None,
                      input_shape: Tuple[int, ...] = (32, 32, 3),
                      n_classes: int = 10, *,
                      n_devices: Optional[int] = None) -> ShardStatic:
    reason = shard_unsupported_reason(flcfg, topo, method, scenario,
                                      n_devices=n_devices)
    if reason is not None:
        raise ValueError(f"sharded engine cannot run this config: {reason}")
    kc, pc = mesh_axes(topo.n_clouds, topo.n_clients, n_devices)
    st = engine_mod.static_from(flcfg, topo, method, scenario,
                                input_shape=input_shape,
                                n_classes=n_classes)
    return ShardStatic(static=st, kc=kc, pc=pc)


# ---------------------------------------------------------------------------
# the compiled sharded engine

@dataclass(frozen=True)
class CompiledShard:
    """Duck-types the scan engine's ``CompiledEngine`` driver surface
    (step / run / init_state / host_round_accounting) so ``FLServer``
    and the simulation drivers treat both engines uniformly."""
    shard_static: ShardStatic
    mesh: Mesh
    step: Callable        # (state, data, t) -> (state, RoundOut)
    run: Callable         # (state, data, rounds) -> (state, RoundOut[T])
    init_state: Callable  # (seed) -> RoundState (mesh-placed)
    stage_data: Callable  # ClientData -> ClientData (mesh-placed)
    d_params: int
    ll_spec: LastLayerSpec
    client_payload: np.ndarray
    edge_payload: np.ndarray

    @property
    def static(self) -> EngineStatic:
        return self.shard_static.static

    def host_round_accounting(self, delivered_rounds: np.ndarray,
                              t0: int = 0) -> np.ndarray:
        return host_round_accounting(self.static, self.d_params,
                                     self.client_payload, self.edge_payload,
                                     delivered_rounds, t0=t0)


def _psum(x, axes=AXES):
    return jax.lax.psum(x, axes)


def _masked_moments(x: Array, w: Array, eps: float = 1e-12
                    ) -> Tuple[Array, Array]:
    """Global per-coordinate (mean, std) over rows with weight ``w`` —
    the shard-decomposed twin of ``core.attacks._honest_moments`` (two
    psum stages: sums for the mean, then centered squares)."""
    n = jnp.maximum(_psum(jnp.sum(w)), 1.0)
    mean = _psum(w @ x) / n
    var = _psum(jnp.sum(((x - mean) ** 2) * w[:, None], axis=0)) / n
    return mean, jnp.sqrt(jnp.maximum(var, eps * eps))


def _shard_attack(name: str, flat: Array, mal: Array, honest_w: Array,
                  *, scale: float, z: float) -> Array:
    """Per-shard update attacks over the local rows. ``mal`` is the
    round's ACTIVE malicious mask restricted to delivered rows;
    ``honest_w`` weights the delivered honest rows (the same set the
    scan engine's ``_honest_moments`` sees)."""
    if name in ("none", "label_flip"):
        return flat
    rm = mal[:, None]
    if name == "sign_flip":
        return jnp.where(rm, -scale * flat, flat)
    if name == "scaling":
        return jnp.where(rm, scale * flat, flat)
    if name == "alie":
        mean, std = _masked_moments(flat, honest_w)
        return jnp.where(rm, mean - z * std, flat)
    if name == "alie_norm":
        eps = 1e-12
        mean, std = _masked_moments(flat, honest_w)
        point = mean - z * std
        # honest MEDIAN norm via the same all_gather idiom as Eq. 7's
        # median damp — (N,)-sized, replicated on every shard
        norms = jnp.linalg.norm(flat, axis=1)
        all_hn = jax.lax.all_gather(
            jnp.where(honest_w > 0, norms, jnp.nan), AXES, tiled=True)
        med = jnp.nanmedian(all_hn)
        med = jnp.where(jnp.isnan(med) | ~(med > 0), 1.0, med)
        point = point * (med / jnp.maximum(jnp.linalg.norm(point), eps))
        return jnp.where(rm, point, flat)
    if name == "ipm":
        mean, _ = _masked_moments(flat, honest_w)
        return jnp.where(rm, -scale * mean, flat)
    if name == "collusion":
        w = mal.astype(flat.dtype)
        n_m = jnp.maximum(_psum(jnp.sum(w)), 1.0)
        mal_mean = _psum(w @ flat) / n_m
        return jnp.where(rm, -scale * mal_mean, flat)
    raise ValueError(f"attack {name!r} is not shard-decomposable")


@lru_cache(maxsize=None)
def compiled_sharded(shard_static: ShardStatic) -> CompiledShard:
    """Build (once per (config, mesh factorization)) the per-shard round
    program and its jitted step / scan drivers."""
    st = shard_static.static
    kc, pc = shard_static.kc, shard_static.pc
    ndev = kc * pc
    topo = st.topology()
    n, k = topo.n_clients, topo.n_clouds
    agg = topo.aggregator_cloud
    n_k = n // k                       # even contiguous layout (gated)
    n_loc = n // ndev
    hier = st.hierarchical
    mesh = client_mesh(k, n, ndev)

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

    _select, m_total = build_select_fn(st)
    _deliver = build_deliver_fn(st)
    _edge_wire = build_edge_wire_fn(lp, k, agg)

    price_arr = jnp.asarray(st.price_multipliers, jnp.float32)
    n_mult = len(st.price_multipliers)
    cp_j = jnp.asarray(client_payload, jnp.float32)
    ep_j = jnp.asarray(edge_payload, jnp.float32)
    cloud_of_j = jnp.asarray(np.array(st.cloud_of))
    f_mal = int(st.malicious_frac * m_total)

    train_loc = jax.vmap(
        lambda p, x, y, kk: client_mod.local_train(
            p, x, y, kk, epochs=st.local_epochs, batch=st.local_batch,
            lr=st.lr),
        in_axes=(None, 0, 0, 0))
    train_ref = jax.vmap(
        lambda p, x, y, kk: client_mod.local_train(
            p, x, y, kk, epochs=st.local_epochs, batch=REF_BATCH, lr=st.lr),
        in_axes=(None, 0, 0, None))

    def _shard_offset():
        """First global client id owned by this shard — the block layout
        of ``P(("cloud", "client"))`` on the leading client axis."""
        shard = (jax.lax.axis_index("cloud") * pc
                 + jax.lax.axis_index("client"))
        return shard * n_loc

    def round_step_local(state: RoundState, data: ClientData, t
                         ) -> Tuple[RoundState, RoundOut]:
        """One round, per-shard view: ``data`` leaves carry this shard's
        (n_loc, ...) client block; (N,)-sized selection state is
        replicated."""
        t = jnp.asarray(t, jnp.int32)
        key = round_key(state.seed, t)
        mult = price_arr[jnp.mod(t, n_mult)] if n_mult > 1 else price_arr[0]
        c_cross_t = st.c_cross * mult
        eps = 1e-12

        # replicated selection + delivery on the full fleet (identical
        # closures — and therefore identical masks — to the scan engine)
        with jax.named_scope("round.select"):
            sel = _select(state.rep_ema, c_cross_t,
                          jax.random.fold_in(key, _FOLD_SELECT))
            delivered = _deliver(sel, jax.random.fold_in(key, _FOLD_DROPOUT))

            i0 = _shard_offset()
            gids = i0 + jnp.arange(n_loc)
            valid = jax.lax.dynamic_slice(delivered, (i0,), (n_loc,))
            rep_loc = jax.lax.dynamic_slice(state.rep_ema, (i0,), (n_loc,))
            w = valid.astype(jnp.float32)

        # masked local training: every local client trains (fixed
        # shapes), each with the same per-client key as the scan engine
        with jax.named_scope("round.train"):
            keys = jax.random.split(key, n)
            keys_loc = jax.lax.dynamic_slice(keys, (i0, 0), (n_loc, 2))
            upd_tree = train_loc(state.params, data.client_x, data.client_y,
                                 keys_loc)
            flat = ravel_rows(upd_tree)                      # (n_loc, D)

        # update attacks on this round's ACTIVE malicious clients
        with jax.named_scope("round.attack"):
            mal = data.malicious
            if st.malice_warmup > 0:
                mal = mal & (t >= st.malice_warmup)
            mal_loc = mal & valid
            flat = _shard_attack(st.attack, flat, mal_loc,
                                 (~mal & valid).astype(jnp.float32),
                                 scale=st.attack_scale, z=st.attack_z)

        # client uplink wire (EF residuals live with the shard)
        res_client = state.res_client
        if client_wire_active:
            with jax.named_scope("round.compress"):
                ckey = jax.random.fold_in(key, _FOLD_CLIENT_WIRE)
                if hier:       # every client→edge hop is intra-class
                    flat, res_client = ef_step_masked(lp.intra, flat,
                                                      res_client, valid, ckey,
                                                      gids)
                else:          # flat path: intra or cross by co-location
                    same = jax.lax.dynamic_slice(
                        (cloud_of_j == agg), (i0,), (n_loc,))
                    flat, res_client = ef_step_masked(
                        lp.intra, flat, res_client, valid & same,
                        jax.random.fold_in(ckey, 0), gids)
                    flat, res_client = ef_step_masked(
                        lp.cross, flat, res_client, valid & ~same,
                        jax.random.fold_in(ckey, 1), gids)

        # everything downstream reads the masked wire view: rows that
        # did not deliver (or were never selected) are exact zeros
        flat = jnp.where(w[:, None] > 0, flat, 0.0)
        ll_loc = flat[:, ll_idx]

        res_edge = state.res_edge
        new_rep = state.rep_ema
        new_feat_sep = state.feat_sep
        feat_w = jnp.zeros((0,), jnp.float32)
        with jax.named_scope("round.aggregate"):
            if hier:
                f32 = flat.dtype
                with jax.named_scope("ref_train"):
                    ref_tree = train_ref(state.params, data.ref_x,
                                         data.ref_y, key)
                    ref_flat = ravel_rows(ref_tree)
                ref_ll = ref_flat[:, ll_idx]
                cloud_loc = gids // n_k                      # (n_loc,)
                onehot = jax.nn.one_hot(cloud_loc, k, dtype=f32)
                ref_ll_loc = ref_ll[cloud_loc]

                # Eq. 7 with the median-damped norm factor: global gbar and
                # the delivered-norm median from cheap (N,)-sized collectives
                wsum = _psum(jnp.sum(w))
                gbar = _psum(w @ ll_loc) / jnp.maximum(wsum, 1.0)
                norms = jnp.linalg.norm(ll_loc, axis=1)
                all_norms = jax.lax.all_gather(
                    jnp.where(w > 0, norms, jnp.nan), AXES, tiled=True)
                med = jnp.nanmedian(all_norms)
                damp = jnp.minimum(1.0, (med / jnp.maximum(norms, eps)) ** 2)
                damp = jnp.where(jnp.isnan(damp), 1.0, damp)
                phi = gradient_contribution(ll_loc, gbar) * damp * w

                # multi-feature gate (core.features): features are per-row
                # (shards own whole rows, gbar/med already globally reduced),
                # the separability statistics reduce in ONE psum of the
                # stacked (6, F) sums, and the EMA/weights stay replicated
                if st.multi_features:
                    feats = feats_mod.client_features(ll_loc, ref_ll_loc,
                                                      gbar, med, w, eps)
                    sums = _psum(feats_mod.separability_sums(feats, w))
                    sep_round = feats_mod.separability_from_sums(sums, eps)
                    new_feat_sep = (
                        feats_mod.FEAT_SEP_RHO * state.feat_sep
                        + (1.0 - feats_mod.FEAT_SEP_RHO) * sep_round)
                    feat_w = feats_mod.feature_weights(new_feat_sep)
                    phi = phi * feats_mod.gate(feats, new_feat_sep)

                # Eq. 8–9
                total = _psum(jnp.sum(phi))
                r = jnp.where(total > eps, phi / jnp.maximum(total, eps),
                              1.0 / n)
                rep_new_loc = (st.ema_gamma * rep_loc
                               + (1.0 - st.ema_gamma) * r)
                rep_new_loc = jnp.where(valid, rep_new_loc, rep_loc)
                new_rep = jax.lax.all_gather(rep_new_loc, AXES, tiled=True)

                # Eq. 11: trust vs. the client's own cloud reference
                dots = jnp.sum(ll_loc * ref_ll_loc, axis=1)
                cos = dots / jnp.maximum(
                    norms * jnp.linalg.norm(ref_ll_loc, axis=1), eps)
                ts = jax.nn.relu(cos) * rep_new_loc * w

                # Eq. 12: rescale to own-cloud reference norm
                ref_norms = jnp.linalg.norm(ref_flat, axis=1)
                g_tilde = flat * (ref_norms[cloud_loc] / jnp.maximum(
                    jnp.linalg.norm(flat, axis=1), eps))[:, None]

                # Eq. 5/13: TWO-STAGE reduction. Stage 1 (intra-cloud): each
                # shard's per-cloud partial sums psum over the client axis —
                # a cloud's clients all live in one mesh column, so this
                # completes the cloud aggregates without crossing columns.
                # Stage 2 (cross-cloud): one combine over the cloud axis
                # (each cloud's rows are nonzero in exactly one column).
                ts_cloud = _psum(onehot.T @ ts)                       # (K,)
                cnt_cloud = _psum(onehot.T @ w)                       # (K,)
                partial = onehot.T @ (g_tilde * ts[:, None])          # (K, D)
                cloud_sums = jax.lax.psum(partial, "client")          # stage 1
                cloud_sums = jax.lax.psum(cloud_sums, "cloud")        # stage 2
                cloud_aggs = cloud_sums / jnp.maximum(ts_cloud, eps)[:, None]
                if edge_wire_active:
                    # edge→global wire on the (now replicated) aggregates —
                    # the SAME shared EF closure as the scan engine, only
                    # `active` is derived from the psum'd per-cloud counts
                    active = (cnt_cloud > 0)[:, None]
                    with jax.named_scope("edge_codec"):
                        cloud_aggs, res_edge = _edge_wire(
                            cloud_aggs, res_edge, active,
                            jax.random.fold_in(key, _FOLD_EDGE_WIRE))
                # empty/zero-trust clouds fall back to their reference update
                cloud_aggs = jnp.where((ts_cloud > eps)[:, None], cloud_aggs,
                                       ref_flat)

                # Eq. 6: cross-cloud phase, β_k from the global reference
                beta = cloud_trust(cloud_aggs, jnp.mean(ref_flat, axis=0))
                update = beta @ cloud_aggs
            else:
                if st.method == "fedavg":
                    update = _psum(w @ flat) / jnp.maximum(_psum(jnp.sum(w)),
                                                           1.0)
                elif st.method == "fltrust":
                    with jax.named_scope("ref_train"):
                        ref_tree = train_ref(state.params, data.ref_x,
                                             data.ref_y, key)
                        ref_flat = ravel_rows(ref_tree)
                    ref = jnp.mean(ref_flat, axis=0)
                    refn = jnp.linalg.norm(ref)
                    norms = jnp.linalg.norm(flat, axis=1)
                    cos = (flat @ ref) / jnp.maximum(norms * refn, eps)
                    ts = jax.nn.relu(cos) * w
                    g_tilde = flat * (refn / jnp.maximum(norms, eps))[:, None]
                    update = (_psum(ts @ g_tilde)
                              / jnp.maximum(_psum(jnp.sum(ts)), eps))
                else:
                    # order statistics need the selected matrix as ONE array:
                    # re-materialize it replicated via a slot-scatter psum —
                    # rows land at their cumsum(sel) position, i.e. the exact
                    # sel_idx order of the scan engine
                    sel_loc = jax.lax.dynamic_slice(sel, (i0,), (n_loc,))
                    slot = jnp.cumsum(sel) - 1                       # (N,)
                    slot_loc = jnp.clip(
                        jax.lax.dynamic_slice(slot, (i0,), (n_loc,)), 0,
                        m_total - 1)
                    buf = jnp.zeros((m_total, flat.shape[1]), flat.dtype)
                    buf = buf.at[slot_loc].add(
                        jnp.where(sel_loc[:, None], flat, 0.0))
                    u = _psum(buf)                                   # (m, D)
                    if st.method == "krum":
                        update = krum(u, f_mal,
                                      multi=max(1, m_total - f_mal - 2))
                    elif st.method == "trimmed_mean":
                        update = trimmed_mean(u,
                                              trim_frac=st.malicious_frac / 2)
                    else:
                        update = coordinate_median(u)

            # apply: w <- w - eta * g  (replicated)
            delta = unflatten_like(update * st.server_lr, state.params)
            params = jax.tree.map(lambda p, g: p - g, state.params, delta)

        # byte-exact wire accounting from the replicated delivered mask —
        # the same reduction as the scan engine, bit-identical masks in,
        # bit-identical bytes out
        with jax.named_scope("round.account"):
            intra_b, cross_b = round_bytes_jax(delivered, cloud_of_j, agg,
                                               cp_j, ep_j, hierarchical=hier)
            cost = (intra_b * st.c_intra + cross_b * c_cross_t) / _GB

        new_state = RoundState(
            params=params, rep_ema=new_rep, res_client=res_client,
            res_edge=res_edge, cum_cost=state.cum_cost + cost,
            cum_intra_bytes=state.cum_intra_bytes + intra_b,
            cum_cross_bytes=state.cum_cross_bytes + cross_b,
            feat_sep=new_feat_sep, seed=state.seed)
        out = RoundOut(delivered=delivered, rep=new_rep, cost=cost,
                       intra_bytes=intra_b, cross_bytes=cross_b,
                       params_l2=tree_l2(params), feat_weights=feat_w)
        return new_state, out

    # --- specs: the client axis of data/residuals is sharded over the
    # mesh; params, reputation and edge residuals are replicated
    sharded_res_client = P(AXES) if client_wire_active else P()
    state_specs = RoundState(
        params=jax.tree.map(lambda _: P(), template),
        rep_ema=P(), res_client=sharded_res_client, res_edge=P(),
        cum_cost=P(), cum_intra_bytes=P(), cum_cross_bytes=P(),
        feat_sep=P(), seed=P())
    data_specs = ClientData(client_x=P(AXES), client_y=P(AXES),
                            ref_x=P(), ref_y=P(), malicious=P(AXES))
    out_specs = (state_specs,
                 RoundOut(delivered=P(), rep=P(), cost=P(),
                          intra_bytes=P(), cross_bytes=P(),
                          params_l2=P(), feat_weights=P()))

    def _program(state, data, ts):
        def body(c, t):
            return round_step_local(c, data, t)
        return jax.lax.scan(body, state, ts)

    def _program_step(state, data, t):
        return round_step_local(state, data, t)

    # manual over both mesh axes
    run_jit = jax.jit(jax.shard_map(
        _program, mesh=mesh, in_specs=(state_specs, data_specs, P()),
        out_specs=out_specs, check_vma=False))
    step_jit = jax.jit(jax.shard_map(
        _program_step, mesh=mesh, in_specs=(state_specs, data_specs, P()),
        out_specs=out_specs, check_vma=False))

    def _place(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            tree, specs)

    def stage_data(data: ClientData) -> ClientData:
        return ClientData(
            client_x=jax.device_put(data.client_x,
                                    NamedSharding(mesh, P(AXES))),
            client_y=jax.device_put(data.client_y,
                                    NamedSharding(mesh, P(AXES))),
            ref_x=jax.device_put(data.ref_x, NamedSharding(mesh, P())),
            ref_y=jax.device_put(data.ref_y, NamedSharding(mesh, P())),
            malicious=jax.device_put(data.malicious,
                                     NamedSharding(mesh, P(AXES))))

    def init_state(seed: int) -> RoundState:
        # the scan engine's round-zero state, plus mesh placement
        state = init_round_state(st, d, seed,
                                 client_wire_active=client_wire_active,
                                 edge_wire_active=edge_wire_active)
        return RoundState(
            params=_place(state.params, state_specs.params),
            rep_ema=jax.device_put(state.rep_ema, NamedSharding(mesh, P())),
            res_client=jax.device_put(
                state.res_client, NamedSharding(mesh, sharded_res_client)),
            res_edge=jax.device_put(state.res_edge,
                                    NamedSharding(mesh, P())),
            cum_cost=jax.device_put(state.cum_cost,
                                    NamedSharding(mesh, P())),
            cum_intra_bytes=jax.device_put(state.cum_intra_bytes,
                                           NamedSharding(mesh, P())),
            cum_cross_bytes=jax.device_put(state.cum_cross_bytes,
                                           NamedSharding(mesh, P())),
            feat_sep=jax.device_put(state.feat_sep,
                                    NamedSharding(mesh, P())),
            seed=jax.device_put(state.seed, NamedSharding(mesh, P())))

    def run(state: RoundState, data: ClientData, rounds: int):
        """scan the sharded engine over ``rounds`` — one device call."""
        return run_jit(state, data, jnp.arange(rounds, dtype=jnp.int32))

    def step(state: RoundState, data: ClientData, t):
        return step_jit(state, data, jnp.asarray(t, jnp.int32))

    return CompiledShard(shard_static=shard_static, mesh=mesh,
                         step=step, run=run, init_state=init_state,
                         stage_data=stage_data, d_params=d, ll_spec=ll,
                         client_payload=client_payload,
                         edge_payload=edge_payload)


def engine_for(flcfg: FLConfig, topo: CloudTopology, data: FederatedData,
               method: str, scenario: Optional[Scenario] = None, *,
               n_devices: Optional[int] = None) -> CompiledShard:
    """Convenience: compile key from (config, data shapes) → engine."""
    ss = static_from_shard(flcfg, topo, method, scenario,
                           input_shape=data.client_x.shape[2:],
                           n_classes=data.n_classes, n_devices=n_devices)
    return compiled_sharded(ss)
