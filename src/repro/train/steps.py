"""Distributed Cost-TrustFL train steps (the paper's Algorithm 1 as a
single jitted SPMD step on the production mesh).

Client/cloud mapping (DESIGN.md §2): clients = data-axis shard groups,
clouds = pods (multi-pod mesh) or contiguous groups of the data axis
(single-pod mesh). Two strategies:

* ``two_phase`` (paper-faithful): ``jax.shard_map`` manual over the data
  axes with the ``model`` axis left to GSPMD (auto). Each shard group
  computes its client's full gradient, Eq. 7–13 run exactly (true
  last-layer gradients, true full-gradient norms), hierarchical weighted
  psums implement Eq. 5–6.

* ``fused`` (beyond-paper): pure GSPMD. Per-client *signatures*
  (final-norm-scale gradient + random-projection sketch of the lm-head
  gradient) are computed from one forward pass; trust weights derived
  from signatures; then ONE backward of the trust-weighted loss yields
  the aggregated update directly. Compatible with FSDP param sharding
  (required for the >=47B architectures).
"""
from __future__ import annotations

import math
import os
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import FLConfig, ModelConfig
from repro.core.selection import select_clients_jax
from repro.core.trust import tree_dot, tree_norm, tree_scale
from repro.models.common import softcap
from repro.models.model import Model
from repro.models import transformer as tfm
from repro.sharding.specs import (data_axes, opt_state_specs, param_specs,
                                  tree_batch_specs)

Array = jax.Array


# ---------------------------------------------------------------------------
# topology helpers

@dataclass(frozen=True)
class MeshTopology:
    """Client/cloud layout derived from the mesh (DESIGN.md §2)."""
    daxes: Tuple[str, ...]        # manual client axes, e.g. ('pod','data')
    n_clients: int
    n_clouds: int
    clients_per_cloud: int
    pod_aligned: bool             # clouds == pods?

    @staticmethod
    def from_mesh(mesh: Mesh, n_clouds: Optional[int] = None
                  ) -> "MeshTopology":
        daxes = data_axes(mesh)
        sizes = [mesh.shape[a] for a in daxes]
        n_clients = int(np.prod(sizes)) if sizes else 1
        if "pod" in mesh.axis_names:
            k = mesh.shape["pod"]
            pod_aligned = True
        else:
            k = n_clouds or min(4, n_clients)
            while n_clients % k:
                k -= 1
            pod_aligned = False
        return MeshTopology(tuple(daxes), n_clients, k, n_clients // k,
                            pod_aligned)

    def cloud_of(self) -> np.ndarray:
        return np.arange(self.n_clients) // self.clients_per_cloud

    def unit_costs(self, c_intra: float, c_cross: float,
                   aggregator_cloud: int = 0) -> np.ndarray:
        """Marginal c_i (Eq. 10) under hierarchical aggregation: intra
        upload to the edge + the cloud's single cross-pod upload amortized
        over its cohorts (see CostModel.hierarchical_unit_costs)."""
        cloud = self.cloud_of()
        edge = np.where(cloud == aggregator_cloud, c_intra, c_cross)
        return c_intra + edge / max(self.clients_per_cloud, 1)


def _cloud_groups(topo: MeshTopology):
    """axis_index_groups for intra-cloud psum on the data axis (only used
    when clouds are virtual subdivisions of a single-pod data axis)."""
    return [list(range(k * topo.clients_per_cloud,
                       (k + 1) * topo.clients_per_cloud))
            for k in range(topo.n_clouds)]


# ---------------------------------------------------------------------------
# shared scoring math

def _last_layer(grads: Dict[str, Any], cfg: ModelConfig) -> Dict[str, Any]:
    """The paper's g^(L): last FC (lm-head / tied embedding) + final norm."""
    out = {"final_norm": grads["final_norm"]}
    out["head"] = grads["lm_head"] if "lm_head" in grads else grads["embed"]
    return out


def _phi(ll: Any, ll_bar: Any, eps: float = 1e-12) -> Array:
    """Eq. 7 on pytrees."""
    dot = tree_dot(ll, ll_bar)
    n_i, n_bar = tree_norm(ll), tree_norm(ll_bar)
    cos = dot / jnp.maximum(n_i * n_bar, eps)
    return jax.nn.relu(cos) * n_i


# ---------------------------------------------------------------------------
# two_phase strategy (paper-faithful, shard_map)

def make_two_phase_step(model: Model, mesh: Mesh, flcfg: FLConfig,
                        optimizer, *, loss_chunk: int = 512
                        ) -> Callable:
    """Returns jitted ``step(params, opt_state, rep, batch, ref_batch)``.

    ``batch``: leaves with leading dim = global_batch, sharded over the
    data axes; each client cohort sees global_batch / n_clients examples.
    ``ref_batch``: leaves with leading dim n_clouds (replicated) — the
    per-cloud trusted reference data (paper §IV-D).
    """
    cfg = model.cfg
    topo = MeshTopology.from_mesh(mesh, flcfg.n_clouds)
    unit_costs = jnp.asarray(topo.unit_costs(flcfg.c_intra, flcfg.c_cross),
                             jnp.float32)
    m_select = min(flcfg.clients_per_round, topo.n_clients)
    _, opt_update = optimizer
    eps = 1e-12

    # NOTE: psums always run in f32 — better reduction numerics, and bf16
    # psum inside shard_map CHECK-crashes the XLA CPU backend used by the
    # dry-run ("Invalid binary instruction opcode copy").
    def intra_psum(x):
        x = x.astype(jnp.float32)
        if topo.pod_aligned:
            return jax.lax.psum(x, "data")
        return jax.lax.psum(x, "data", axis_index_groups=_cloud_groups(topo))

    def cross_sum(x):
        """Sum of one representative value per cloud (values are uniform
        within a cloud after intra_psum)."""
        x = x.astype(jnp.float32)
        if topo.pod_aligned:
            return jax.lax.psum(x, "pod")
        return jax.lax.psum(x, "data") / topo.clients_per_cloud

    def all_sum(x):
        return jax.lax.psum(x.astype(jnp.float32), topo.daxes)

    def client_index():
        if len(topo.daxes) == 2:
            return (jax.lax.axis_index(topo.daxes[0])
                    * jax.lax.axis_size(topo.daxes[1])
                    + jax.lax.axis_index(topo.daxes[1]))
        return jax.lax.axis_index(topo.daxes[0])

    def per_group(params, rep, batch, ref_batch):
        idx = client_index()
        cloud = idx // topo.clients_per_cloud

        loss_of = lambda p, b: model.loss(p, b, loss_chunk)[0]
        # line 8: LocalTrain -> client gradient (one local step; the
        # simulation substrate runs multi-epoch SGD, the production step
        # uses the gradient form of Alg. 1)
        loss_i, g_i = jax.value_and_grad(loss_of)(params, batch)
        # line 10: per-cloud reference gradient on the trusted set
        ref_b = jax.tree.map(lambda x: x[cloud], ref_batch)
        g_ref = jax.grad(loss_of)(params, ref_b)

        # --- Eq. 7–9: reputation from last-layer gradients
        ll_i = _last_layer(g_i, cfg)
        ll_ref = _last_layer(g_ref, cfg)
        ll_bar = jax.tree.map(lambda x: all_sum(x) / topo.n_clients, ll_i)
        phi_i = _phi(ll_i, ll_bar)
        onehot = jax.nn.one_hot(idx, topo.n_clients, dtype=jnp.float32)

        # --- Eq. 10: cost-aware selection from last round's reputation
        sel_mask = select_clients_jax(rep, unit_costs, m_select,
                                      flcfg.cost_lambda)
        sel_i = sel_mask[idx].astype(jnp.float32)

        phi_i = phi_i * sel_i
        phi_sum = all_sum(phi_i)
        r_i = jnp.where(phi_sum > eps, phi_i / jnp.maximum(phi_sum, eps),
                        1.0 / topo.n_clients)
        r_vec = all_sum(onehot * r_i)
        new_rep = jnp.where(sel_mask,
                            flcfg.ema_gamma * rep
                            + (1 - flcfg.ema_gamma) * r_vec, rep)

        # --- Eq. 11: trust score vs own-cloud reference
        cos_ref = tree_dot(ll_i, ll_ref) / jnp.maximum(
            tree_norm(ll_i) * tree_norm(ll_ref), eps)
        ts_i = jax.nn.relu(cos_ref) * new_rep[idx] * sel_i

        # --- Eq. 12: normalize to reference gradient magnitude
        gn_i = tree_norm(g_i)
        gn_ref = tree_norm(g_ref)
        rescale = gn_ref / jnp.maximum(gn_i, eps)

        # --- Eq. 5 + 13 intra-cloud combine, computed PER LEAF so only
        # one leaf's f32 temporaries are live at a time (whole-tree
        # staging kept ~5 full f32 gradient copies resident — §Perf)
        ts_cloud = intra_psum(ts_i)

        def leaf_cloud(gi, gr):
            gc = intra_psum(gi.astype(jnp.float32) * (rescale * ts_i)) \
                / jnp.maximum(ts_cloud, eps)
            return jnp.where(ts_cloud > eps, gc, gr.astype(jnp.float32))

        g_cloud = jax.tree.map(leaf_cloud, g_i, g_ref)

        # --- Eq. 6: cross-cloud combine with cloud trust beta_k
        ll_cloud = _last_layer(g_cloud, cfg)
        ll_gref = jax.tree.map(lambda x: cross_sum(x) / topo.n_clouds,
                               ll_ref)
        beta_k = jax.nn.relu(tree_dot(ll_cloud, ll_gref) / jnp.maximum(
            tree_norm(ll_cloud) * tree_norm(ll_gref), eps))
        beta_sum = cross_sum(beta_k)
        beta_n = jnp.where(beta_sum > eps, beta_k / jnp.maximum(beta_sum, eps),
                           1.0 / topo.n_clouds)
        g_global = jax.tree.map(lambda x: cross_sum(x * beta_n), g_cloud)

        metrics = {
            "loss": all_sum(loss_i * sel_i) / jnp.maximum(all_sum(sel_i), 1.0),
            "phi": all_sum(onehot * phi_i),
            "trust": all_sum(onehot * ts_i),
            "beta": beta_n,
            "selected": sel_mask.astype(jnp.float32),
            "round_cost_units": jnp.sum(sel_mask * unit_costs),
        }
        return g_global, new_rep, metrics

    params_shape = jax.eval_shape(lambda k: model.init(k),
                                  jax.random.PRNGKey(0))
    pspecs = param_specs(params_shape, cfg, mesh)
    dax = topo.daxes if len(topo.daxes) > 1 else topo.daxes[0]

    def step(params, opt_state, rep, batch, ref_batch):
        # manual over the data axes; the model axis stays auto (GSPMD)
        mapped = jax.shard_map(
            per_group, mesh=mesh,
            in_specs=(P(), P(), P(dax), P()),
            out_specs=(P(), P(), P()),
            axis_names=set(topo.daxes), check_vma=False,
        )
        g_global, new_rep, metrics = mapped(params, rep, batch, ref_batch)
        # optimizer update at GSPMD level: ZeRO-1 — moments are sharded
        # over the data axes (opt_state_specs); g_global is replicated
        new_params, new_opt = opt_update(g_global, opt_state, params)
        return new_params, new_opt, new_rep, metrics

    opt_shape = jax.eval_shape(optimizer[0], params_shape)
    ospecs = opt_state_specs(opt_shape, params_shape, cfg, mesh)
    donate = () if os.environ.get("REPRO_NO_DONATE") else (0, 1)
    p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    o_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), ospecs)
    return jax.jit(
        step,
        in_shardings=(p_sh, o_sh, None, None, None),
        # pin outputs so step(step(...)) round-trips without resharding
        out_shardings=(p_sh, o_sh, None, None),
        donate_argnums=donate,
    ), topo


# ---------------------------------------------------------------------------
# fused strategy (beyond-paper, pure GSPMD + signatures)

def _signatures(params, cfg: ModelConfig, batch, n_clients: int,
                sketch_dim: int, key: Array, loss_chunk: int = 512
                ) -> Tuple[Array, Array, Array]:
    """One forward pass -> per-client (loss, signature, signature-norm).

    signature_i = [ vec(Σ_t h_t ⊗ ((p_t − y_t) Ω)) ;  dL/dγ_final ]
    where Ω is a fixed (vocab, sketch) Rademacher projection — a JL sketch
    of the true lm-head gradient Σ_t h_t ⊗ (p_t − y_t).
    Shapes: losses (N,), signatures (N, D·s + D).
    """
    from repro.sharding.constrain import constrain
    h, aux, off = tfm.forward_hidden(params, cfg, batch)
    h = h[:, off:]
    tokens, labels = batch["tokens"], batch["labels"]
    mask = batch["mask"].astype(jnp.float32)
    b, s, d = h.shape
    per = b // n_clients

    # client-major layout: the client dim (N) aligns with the mesh data
    # axes exactly like the two_phase strategy's shard groups, so all
    # per-client reductions stay local (no cross-client collectives)
    def cm(x):
        return constrain(x.reshape((n_clients, per) + x.shape[1:]),
                         {0: ("pod", "data")})
    h = cm(h)                                          # (N, per, S, D)
    labels_c, mask_c = cm(labels), cm(mask)

    omega = (2.0 * jax.random.bernoulli(
        key, 0.5, (cfg.vocab_size, sketch_dim)).astype(jnp.float32) - 1.0
             ) / math.sqrt(sketch_dim)

    chunk = min(loss_chunk, s)
    n_chunks = max(1, s // chunk)
    s_trunc = n_chunks * chunk

    def body(carry, xs):
        losses, sk = carry
        hc, yc, mc = xs              # (N,per,c,D),(N,per,c),(N,per,c)
        logits = tfm.logits_fn(params, cfg, hc)
        logits = constrain(logits, {0: ("pod", "data"), 3: "model"})
        logits = softcap(logits, cfg.logit_softcap).astype(jnp.float32)
        p = jax.nn.softmax(logits, axis=-1)
        gold = jax.nn.one_hot(yc, cfg.vocab_size, dtype=jnp.float32)
        dl = constrain((p - gold) * mc[..., None],
                       {0: ("pod", "data"), 3: "model"})  # (N,per,c,V)
        nll = (jax.nn.logsumexp(logits, -1)
               - jnp.take_along_axis(logits, yc[..., None], -1)[..., 0]) * mc
        losses = losses + jnp.sum(nll, axis=(1, 2))
        z = constrain(dl @ omega, {0: ("pod", "data")})   # (N,per,c,s̃)
        sk_c = jnp.einsum("nptd,npts->nds", hc, z)
        return (losses, sk + sk_c), None

    hs = h[:, :, :s_trunc].reshape(n_clients, per, n_chunks, chunk, d)
    ys = labels_c[:, :, :s_trunc].reshape(n_clients, per, n_chunks, chunk)
    ms = mask_c[:, :, :s_trunc].reshape(n_clients, per, n_chunks, chunk)
    init = (jnp.zeros((n_clients,), jnp.float32),
            jnp.zeros((n_clients, d, sketch_dim), jnp.float32))
    (losses, sk), _ = jax.lax.scan(
        body, init, (jnp.moveaxis(hs, 2, 0), jnp.moveaxis(ys, 2, 0),
                     jnp.moveaxis(ms, 2, 0)))

    tok_per_client = jnp.sum(mask_c, axis=(1, 2))
    losses = losses / jnp.maximum(tok_per_client, 1.0)
    sigs = sk.reshape(n_clients, -1) / jnp.maximum(tok_per_client, 1.0
                                                   )[:, None]
    return losses, sigs, jnp.linalg.norm(sigs, axis=1)


def make_fused_step(model: Model, mesh: Mesh, flcfg: FLConfig, optimizer,
                    *, loss_chunk: int = 512) -> Callable:
    """Signature-fused Cost-TrustFL: GSPMD-only, FSDP-compatible."""
    cfg = model.cfg
    topo = MeshTopology.from_mesh(mesh, flcfg.n_clouds)
    unit_costs = jnp.asarray(topo.unit_costs(flcfg.c_intra, flcfg.c_cross),
                             jnp.float32)
    m_select = min(flcfg.clients_per_round, topo.n_clients)
    _, opt_update = optimizer
    cloud_of = jnp.asarray(topo.cloud_of())
    k_clouds = topo.n_clouds
    eps = 1e-12

    def step(params, opt_state, rep, batch, ref_batch, key):
        n = topo.n_clients
        # --- per-client signatures from ONE forward pass
        if os.environ.get("REPRO_FUSED_NOSIG"):       # debug isolation
            losses = jnp.ones((n,), jnp.float32)
            sigs = jnp.ones((n, 8), jnp.float32)
            signorm = jnp.linalg.norm(sigs, axis=1)
        else:
            losses, sigs, signorm = _signatures(params, cfg, batch, n,
                                                flcfg.sketch_dim, key,
                                                loss_chunk)
        # per-cloud reference signatures (tiny forward per cloud)
        if os.environ.get("REPRO_FUSED_NOSIG"):
            ref_sigs_all = jnp.ones((k_clouds, sigs.shape[1]), jnp.float32)
            ref_norms_all = jnp.linalg.norm(ref_sigs_all, axis=1)
        else:
            ref_flat = jax.tree.map(
                lambda x: x.reshape((-1,) + x.shape[2:]), ref_batch)
            _, ref_sigs_all, ref_norms_all = _signatures(
                params, cfg, ref_flat, k_clouds, flcfg.sketch_dim, key,
                loss_chunk)

        # --- Eq. 7–9 on signatures
        sig_bar = jnp.mean(sigs, axis=0)
        cos_bar = (sigs @ sig_bar) / jnp.maximum(
            signorm * jnp.linalg.norm(sig_bar), eps)
        sel_mask = select_clients_jax(rep, unit_costs, m_select,
                                      flcfg.cost_lambda)
        sel = sel_mask.astype(jnp.float32)
        phi = jax.nn.relu(cos_bar) * signorm * sel
        r = jnp.where(jnp.sum(phi) > eps, phi / jnp.maximum(jnp.sum(phi), eps),
                      1.0 / n)
        new_rep = jnp.where(sel_mask, flcfg.ema_gamma * rep
                            + (1 - flcfg.ema_gamma) * r, rep)

        # --- Eq. 11 vs own-cloud reference signature
        ref_sig = ref_sigs_all[cloud_of]                     # (N, Ds)
        cos_ref = jnp.sum(sigs * ref_sig, axis=1) / jnp.maximum(
            signorm * jnp.linalg.norm(ref_sig, axis=1), eps)
        ts = jax.nn.relu(cos_ref) * new_rep * sel
        # degenerate round (every cosine <= 0, e.g. uninformative sketches):
        # fall back to reputation-weighted FedAvg over the selected clients
        # rather than emitting a zero update — mirrors the zero-trust-cloud
        # fallback in cost_trustfl_aggregate
        ts = jnp.where(jnp.sum(ts) > eps, ts, new_rep * sel)

        # --- Eq. 12 proxy: signature-norm normalization
        ref_norm = ref_norms_all[cloud_of]
        scale_i = ref_norm / jnp.maximum(signorm, eps)

        # --- Eq. 5/13 weights + Eq. 6 beta, all in weight space
        cloud_onehot = jax.nn.one_hot(cloud_of, k_clouds,
                                      dtype=jnp.float32)     # (N, K)
        ts_cloud = cloud_onehot.T @ ts                        # (K,)
        # cloud aggregate signature direction for beta
        agg_sig = cloud_onehot.T @ (sigs * (ts * scale_i)[:, None])
        agg_sig = agg_sig / jnp.maximum(ts_cloud, eps)[:, None]
        gref_sig = jnp.mean(ref_sigs_all, axis=0)
        beta = jax.nn.relu(
            (agg_sig @ gref_sig) / jnp.maximum(
                jnp.linalg.norm(agg_sig, axis=1)
                * jnp.linalg.norm(gref_sig), eps))
        beta = jnp.where(jnp.sum(beta) > eps,
                         beta / jnp.maximum(jnp.sum(beta), eps),
                         1.0 / k_clouds)

        w = (beta[cloud_of] * ts * scale_i
             / jnp.maximum(ts_cloud[cloud_of], eps))          # (N,)

        # --- ONE backward of the trust-weighted loss
        per = batch["tokens"].shape[0] // n
        w_example = jnp.repeat(w, per)                        # (B,)

        def weighted_loss(p):
            h, aux, off = tfm.forward_hidden(p, cfg, batch)
            h = h[:, off:]
            mask = batch["mask"].astype(jnp.float32) \
                * jax.lax.stop_gradient(w_example)[:, None]
            from repro.models.common import chunked_cross_entropy
            lm = chunked_cross_entropy(
                lambda hc: tfm.logits_fn(p, cfg, hc), h, batch["labels"],
                mask, chunk=loss_chunk, logit_softcap_val=cfg.logit_softcap)
            return lm + aux

        g = jax.grad(weighted_loss)(params)
        new_params, new_opt = opt_update(g, opt_state, params)
        metrics = {
            "loss": jnp.sum(losses * sel) / jnp.maximum(jnp.sum(sel), 1.0),
            "phi": phi, "trust": ts, "beta": beta,
            "selected": sel,
            "round_cost_units": jnp.sum(sel * unit_costs),
        }
        return new_params, new_opt, new_rep, metrics

    params_shape = jax.eval_shape(lambda k: model.init(k),
                                  jax.random.PRNGKey(0))
    pspecs = param_specs(params_shape, cfg, mesh)
    opt_shape = jax.eval_shape(optimizer[0], params_shape)
    ospecs = opt_state_specs(opt_shape, params_shape, cfg, mesh)
    donate = () if os.environ.get("REPRO_NO_DONATE") else (0, 1)
    p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    o_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), ospecs)
    return jax.jit(
        step,
        in_shardings=(p_sh, o_sh, None, None, None, None),
        out_shardings=(p_sh, o_sh, None, None),
        donate_argnums=donate,
    ), topo


def make_fl_train_step(model: Model, mesh: Mesh, flcfg: FLConfig, optimizer,
                       *, strategy: Optional[str] = None,
                       loss_chunk: int = 512):
    strategy = strategy or model.cfg.fl_strategy
    if strategy == "two_phase":
        return make_two_phase_step(model, mesh, flcfg, optimizer,
                                   loss_chunk=loss_chunk)
    return make_fused_step(model, mesh, flcfg, optimizer,
                           loss_chunk=loss_chunk)


# ---------------------------------------------------------------------------
# plain (non-FL) train step — baseline substrate

def make_plain_step(model: Model, mesh: Optional[Mesh], optimizer,
                    loss_chunk: int = 512):
    _, opt_update = optimizer

    def step(params, opt_state, batch):
        (loss, metrics), g = model.grad_fn(loss_chunk)(params, batch)
        new_params, new_opt = opt_update(g, opt_state, params)
        return new_params, new_opt, {"loss": loss, **metrics}

    return jax.jit(step, donate_argnums=(0, 1))
