"""Plain reference of the Cost-TrustFL round, written from the paper's
equations and the configuration's sizes alone.

It imports nothing of the program and takes nothing the program made:
weights come from the seed (He-normal, as the configuration states),
inputs from ``bench.datagen``. Every step is straightforward ``jax.numpy``
at one dtype and matmul precision, so the same code gives the reference
(float32 at ``HIGHEST``) and the precision control (bfloat16 throughout).

The round, for a fleet of K clouds and a selected set of m clients:

* Eq. 10 selection: the top-m of r_i / c_i**lambda with a per-cloud
  exploration quota and 1e-4 multiplicative tie-break noise;
* LocalTrain: E epochs of minibatch SGD (batch B) from the broadcast
  weights; the update is w_global - w_local;
* the update attack on the round's malicious rows;
* the per-link top-k codec with error feedback (values travel as fp16);
* Eq. 7-9 reputation: phi_i = ReLU(cos(g_i, gbar)) * ||g_i|| on the last
  layer, damped past the median norm, normalised and EMA-smoothed;
* Eq. 11-13 intra-cloud aggregation (trust vs the cloud's reference
  update, rescaled to its norm) and Eq. 5-6 cross-cloud combination;
* Eq. 1-4 wire bytes and dollars in float64.

Randomness follows the seeded key schedule the configuration states: the
round key is ``PRNGKey(int32(seed * 7919 + t))``; selection noise folds
in 131; client i trains on ``split(key, N)[i]``, the reference trainings
on ``key`` itself. The top-k codec draws nothing.
"""
from __future__ import annotations

from dataclasses import dataclass, fields
from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

GB = 1024.0 ** 3
# the configuration's ``matmul_precision`` by name
PRECISIONS = {"default": lax.Precision.DEFAULT, "high": lax.Precision.HIGH,
              "highest": lax.Precision.HIGHEST}
EPS = 1e-12
FOLD_SELECT = 131
LEAVES = ("conv1_b", "conv1_w", "conv2_b", "conv2_w",
          "fc1_b", "fc1_w", "fc2_b", "fc2_w")   # flat order: sorted names


# planted faults: each SGD step leaves out half its minibatch (the mean
# over the rest) in the clients' and the references' training, or in the
# clients' alone; the client error-feedback residual is never added back
FAULTS = ("half_batch", "half_batch_clients", "no_ef_client")


@dataclass(frozen=True)
class Job:
    """Everything a round depends on, from the configuration and the
    traffic mix."""
    input_shape: Tuple[int, int, int]
    n_classes: int
    conv_channels: Tuple[int, int]
    fc_width: int
    n_clouds: int
    clients_per_cloud: int
    clients_per_round: int
    local_epochs: int
    local_batch: int
    ref_batch: int
    lr: float
    server_lr: float
    ema_gamma: float
    cost_lambda: float
    c_intra: float
    c_cross: float
    attack: str
    attack_scale: float
    malicious_frac: float
    compressor: str
    compress_ratio: float
    link_policy: str
    aggregator_cloud: int = 0
    # a planted fault, for reading what a broken program would read (see
    # FAULTS); "" is the round as the paper states it
    fault: str = ""

    @property
    def n_clients(self) -> int:
        return self.n_clouds * self.clients_per_cloud

    @property
    def cloud_of(self) -> np.ndarray:
        return np.repeat(np.arange(self.n_clouds), self.clients_per_cloud)

    @property
    def quota(self) -> int:
        return 2 if self.cost_lambda < 0.75 else 0

    @property
    def m_total(self) -> int:
        m = min(self.clients_per_round, self.n_clients)
        if not self.quota:
            return m
        return max(m, self.n_clouds * min(self.quota, self.clients_per_cloud))

    def leaf_shapes(self) -> Dict[str, Tuple[int, ...]]:
        h, w, c = self.input_shape
        c1, c2 = self.conv_channels
        flat = (h // 4) * (w // 4) * c2
        return {"conv1_w": (3, 3, c, c1), "conv1_b": (c1,),
                "conv2_w": (3, 3, c1, c2), "conv2_b": (c2,),
                "fc1_w": (flat, self.fc_width), "fc1_b": (self.fc_width,),
                "fc2_w": (self.fc_width, self.n_classes),
                "fc2_b": (self.n_classes,)}

    @property
    def d_params(self) -> int:
        return int(sum(np.prod(s) for s in self.leaf_shapes().values()))

    # -- the codec per link class ------------------------------------------
    def codec(self, link: str) -> str:
        """'none' or 'topk' on the 'intra' or 'cross' link class."""
        if self.compressor == "none" or self.link_policy == "none":
            return "none"
        on = {"all": ("intra", "cross"), "cross_only": ("cross",),
              "intra_only": ("intra",)}[self.link_policy]
        return self.compressor if link in on else "none"

    def topk_k(self) -> int:
        d = self.d_params
        return max(1, min(d, int(round(self.compress_ratio * d))))

    def payload(self, link: str) -> float:
        if self.codec(link) == "topk":
            return float(4 + 6 * self.topk_k())
        if self.codec(link) != "none":
            raise ValueError(f"no reference for compressor {self.compressor!r}")
        return 4.0 * self.d_params


def job_for(config: Dict, traffic: Dict, ref_batch: int) -> Job:
    """The Job of a configuration file and a traffic mix. Raises for a
    mix the reference does not model: another method or trust feature,
    or a traffic key that is no field of Job."""
    if (traffic.get("method") != "cost_trustfl"
            or traffic.get("trust_features", "scalar") != "scalar"):
        raise ValueError("the reference models cost_trustfl with scalar trust")
    keys = set(traffic) - {"method", "trust_features", "why"}
    modelled = {f.name for f in fields(Job)} - {
        "input_shape", "n_classes", "conv_channels", "fc_width", "ref_batch",
        "fault"}
    if keys - modelled:
        raise ValueError("the reference does not model the traffic keys "
                         f"{sorted(keys - modelled)}")
    return Job(input_shape=tuple(config["input_shape"]),
               n_classes=int(config["n_classes"]),
               conv_channels=tuple(config["conv_channels"]),
               fc_width=int(config["fc_width"]), ref_batch=ref_batch,
               **{k: traffic[k] for k in keys})


def round_key(seed: int, t: int) -> jax.Array:
    v = (int(seed) * 7919 + int(t)) & 0xFFFFFFFF
    v = v - (1 << 32) if v >= (1 << 31) else v
    return jax.random.PRNGKey(np.int32(v))


def init_params(job: Job, seed: int) -> Dict[str, jax.Array]:
    """He-normal weights and zero biases from ``PRNGKey(seed)``, one key
    per weight in the order conv1, conv2, fc1, fc2."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shapes = job.leaf_shapes()
    out = {}
    for k, name in zip(ks, ("conv1_w", "conv2_w", "fc1_w", "fc2_w")):
        shape = shapes[name]
        fan_in = int(np.prod(shape[:-1]))
        out[name] = jax.random.normal(k, shape) * jnp.sqrt(2.0 / fan_in)
        out[name.replace("_w", "_b")] = jnp.zeros(shape[-1:])
    return out


# ---------------------------------------------------------------------------
# the model and LocalTrain

def _conv(x, w, b, precision):
    y = lax.conv_general_dilated(x, w, (1, 1), "SAME",
                                 dimension_numbers=("NHWC", "HWIO", "NHWC"),
                                 precision=precision)
    return jax.nn.relu(y + b)


def _pool(x):
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def forward(p, x, precision):
    x = _pool(_conv(x, p["conv1_w"], p["conv1_b"], precision))
    x = _pool(_conv(x, p["conv2_w"], p["conv2_b"], precision))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(jnp.dot(x, p["fc1_w"], precision=precision) + p["fc1_b"])
    return jnp.dot(x, p["fc2_w"], precision=precision) + p["fc2_b"]


def _loss(p, x, y, precision):
    logits = forward(p, x, precision)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def local_update(p, x, y, key, *, epochs, batch, lr, precision,
                 half_batch=False):
    """w - w_local after E epochs of SGD on minibatches drawn with
    replacement from the client's S samples, floor(S/B) steps an epoch."""
    n = x.shape[0]
    steps = epochs * max(1, n // batch)

    def step(w, k):
        ix = jax.random.randint(k, (batch,), 0, n)
        if half_batch:
            ix = ix[:batch // 2]
        g = jax.grad(_loss)(w, x[ix], y[ix], precision)
        return jax.tree.map(lambda a, b: a - lr * b, w, g), None

    local, _ = lax.scan(step, p, jax.random.split(key, steps))
    return jax.tree.map(lambda a, b: a - b, p, local)


def flat_rows(tree) -> jax.Array:
    b = tree[LEAVES[0]].shape[0]
    return jnp.concatenate([tree[n].reshape(b, -1) for n in LEAVES], axis=1)


def unflat(vec, job: Job):
    shapes = job.leaf_shapes()
    out, off = {}, 0
    for n in LEAVES:
        size = int(np.prod(shapes[n]))
        out[n] = vec[off:off + size].reshape(shapes[n])
        off += size
    return out


def last_layer(flat, job: Job):
    """The last FC layer's weight then bias, from (m, D) flat rows."""
    d = job.d_params
    nb, nw = job.n_classes, job.fc_width * job.n_classes
    return jnp.concatenate([flat[:, d - nw:], flat[:, d - nw - nb:d - nw]],
                           axis=1)


# ---------------------------------------------------------------------------
# the round

def select(job: Job, rep, key) -> np.ndarray:
    """Eq. 10 with the per-cloud quota; ties go to the lower index."""
    sizes = np.full(job.n_clouds, job.clients_per_cloud, np.float32)
    prices = np.full(job.n_clouds, job.c_cross, np.float32)
    prices[job.aggregator_cloud] = job.c_intra
    unit = jnp.float32(job.c_intra) + jnp.asarray(prices / sizes)[
        jnp.asarray(job.cloud_of)]
    ratio = rep.astype(jnp.float32) / unit ** jnp.float32(job.cost_lambda)
    ratio = ratio * (1.0 + 1e-4 * jax.random.normal(
        jax.random.fold_in(key, FOLD_SELECT), ratio.shape, jnp.float32))
    ratio = np.asarray(ratio)
    chosen = np.zeros(job.n_clients, bool)
    for k in range(job.n_clouds):
        idx = np.nonzero(job.cloud_of == k)[0]
        q = min(job.quota, idx.size)
        chosen[idx[np.argsort(-ratio[idx], kind="stable")[:q]]] = True
    rest = job.m_total - int(chosen.sum())
    if rest > 0:
        order = np.argsort(-np.where(chosen, -np.inf, ratio), kind="stable")
        chosen[order[:rest]] = True
    return chosen


def topk_roundtrip(y, k):
    """Keep each row's k largest magnitudes (ties at the threshold kept)
    and pass the kept values through fp16, as the wire carries them."""
    thr = jnp.sort(jnp.abs(y), axis=1)[:, -k][:, None]
    kept = jnp.where(jnp.abs(y) >= thr, y, jnp.zeros_like(y))
    return kept.astype(jnp.float16).astype(y.dtype)


def wire_bytes(job: Job, delivered: np.ndarray) -> Tuple[float, float, float]:
    """(intra bytes, cross bytes, dollars) of one round, float64."""
    cp = np.full(job.n_clients, job.payload("intra"), np.float64)
    ep = np.full(job.n_clouds, job.payload("cross"), np.float64)
    ep[job.aggregator_cloud] = job.payload("intra")
    intra = float(cp[delivered].sum())
    active = np.bincount(job.cloud_of[delivered],
                         minlength=job.n_clouds) > 0
    ep = ep * active
    cross = float(ep.sum() - ep[job.aggregator_cloud])
    intra += float(ep[job.aggregator_cloud])
    dollars = float((intra * job.c_intra + cross * job.c_cross) / GB)
    return intra, cross, dollars


def _norm(x, axis=None):
    return jnp.sqrt(jnp.sum(x * x, axis=axis))


@partial(jax.jit, static_argnames=("job", "precision", "dtype"))
def _train_and_aggregate(job: Job, precision, dtype, params, rep, res_client,
                         res_edge, cx, cy, rx, ry, mal_sel, sel_idx, key):
    """One round after selection, for the m selected clients."""
    n, k = job.n_clients, job.n_clouds
    cast = lambda a: a.astype(dtype)
    params = jax.tree.map(cast, params)
    keys = jax.random.split(key, n)[sel_idx]
    train = partial(local_update, epochs=job.local_epochs,
                    batch=job.local_batch, lr=job.lr, precision=precision,
                    half_batch=job.fault in ("half_batch",
                                             "half_batch_clients"))
    upd = jax.vmap(train, in_axes=(None, 0, 0, 0))(params, cast(cx), cy, keys)
    flat = flat_rows(upd)
    if job.attack == "sign_flip":
        flat = jnp.where(mal_sel[:, None], -job.attack_scale * flat, flat)
    elif job.attack not in ("none", "label_flip"):
        raise ValueError(f"no reference for attack {job.attack!r}")

    if job.codec("intra") == "topk":
        y = flat + cast(res_client[sel_idx])
        if job.fault == "no_ef_client":
            y = flat
        flat = topk_roundtrip(y, job.topk_k())
        res_client = res_client.at[sel_idx].set((y - flat).astype(res_client.dtype))

    refs = jax.vmap(partial(local_update, epochs=job.local_epochs,
                            batch=job.ref_batch, lr=job.lr,
                            precision=precision,
                            half_batch=job.fault == "half_batch"),
                    in_axes=(None, 0, 0, None))(params, cast(rx), ry, key)
    ref_flat = flat_rows(refs)

    ll = last_layer(flat, job)
    ref_ll = last_layer(ref_flat, job)
    cloud = jnp.asarray(job.cloud_of)[sel_idx]
    gbar = jnp.mean(ll, axis=0)
    norms = _norm(ll, axis=1)
    med = jnp.median(norms)
    damp = jnp.minimum(1.0, (med / jnp.maximum(norms, EPS)) ** 2)
    cos_g = (ll @ gbar) / jnp.maximum(norms * _norm(gbar), EPS)
    phi = jax.nn.relu(cos_g) * norms * damp                       # Eq. 7
    total = jnp.sum(phi)
    r = jnp.where(total > EPS, phi / jnp.maximum(total, EPS),
                  jnp.asarray(1.0 / n, phi.dtype))               # Eq. 8
    rep_sel = job.ema_gamma * rep[sel_idx].astype(dtype) + (1 - job.ema_gamma) * r
    new_rep = rep.at[sel_idx].set(rep_sel.astype(rep.dtype))     # Eq. 9

    own_ref = ref_ll[cloud]
    cos_r = jnp.sum(ll * own_ref, axis=1) / jnp.maximum(
        norms * _norm(own_ref, axis=1), EPS)
    ts = jax.nn.relu(cos_r) * rep_sel                             # Eq. 11
    ref_norms = _norm(ref_flat, axis=1)
    g_t = flat * (ref_norms[cloud] / jnp.maximum(_norm(flat, axis=1),
                                                 EPS))[:, None]  # Eq. 12
    aggs, ts_cloud = [], []
    for c in range(k):
        wc = jnp.where(cloud == c, ts, 0.0)
        tc = jnp.sum(wc)
        aggs.append((wc @ g_t) / jnp.maximum(tc, EPS))             # Eq. 13
        ts_cloud.append(tc)
    aggs = jnp.stack(aggs)
    ts_cloud = jnp.stack(ts_cloud)
    if job.codec("intra") != "none" or job.codec("cross") != "none":
        present = jnp.stack([jnp.any(cloud == c) for c in range(k)])
        y = aggs + cast(res_edge)
        sent = []
        for c in range(k):
            link = "intra" if c == job.aggregator_cloud else "cross"
            sent.append(topk_roundtrip(y[c:c + 1], job.topk_k())[0]
                        if job.codec(link) == "topk" else y[c])
        sent = jnp.stack(sent)
        on = present[:, None]
        res_edge = jnp.where(on, y - sent, cast(res_edge)).astype(res_edge.dtype)
        aggs = jnp.where(on, sent, aggs)
    aggs = jnp.where((ts_cloud > EPS)[:, None], aggs, ref_flat)
    gref = jnp.mean(ref_flat, axis=0)
    cos_c = (aggs @ gref) / jnp.maximum(_norm(aggs, axis=1) * _norm(gref), EPS)
    beta = jax.nn.relu(cos_c)                                     # Eq. 6
    bsum = jnp.sum(beta)
    beta = jnp.where(bsum > EPS, beta / jnp.maximum(bsum, EPS),
                     jnp.full((k,), 1.0 / k, beta.dtype))
    update = beta @ aggs
    new_params = jax.tree.map(lambda w, g: (w - g).astype(jnp.float32),
                              params, unflat(update * job.server_lr, job))
    return new_params, new_rep, res_client, res_edge


def run_rounds(job: Job, seed: int, client_x, client_y, ref_x, ref_y,
               malicious: np.ndarray, rounds: int, *,
               dtype=jnp.float32, precision=lax.Precision.HIGHEST
               ) -> List[Dict[str, np.ndarray]]:
    """``rounds`` rounds from the seed's initial weights. Entry 0 holds the
    initial parameters; entry t + 1 round t's delivered mask, wire bytes,
    dollars, reputation, parameters (host arrays) and the norm of the
    client-uplink error-feedback table."""
    params = init_params(job, seed)
    n, d = job.n_clients, job.d_params
    rep = jnp.full((n,), 1.0 / n, jnp.float32)
    res_client = jnp.zeros((n, d), jnp.float32)
    res_edge = jnp.zeros((job.n_clouds, d), jnp.float32)
    cx, cy = jnp.asarray(client_x), jnp.asarray(client_y)
    rx, ry = jnp.asarray(ref_x), jnp.asarray(ref_y)
    out = [{"params": jax.tree.map(np.asarray, params)}]
    for t in range(rounds):
        key = round_key(seed, t)
        sel = select(job, rep, key)
        sel_idx = np.nonzero(sel)[0]
        params, rep, res_client, res_edge = _train_and_aggregate(
            job, precision, dtype, params, rep, res_client, res_edge,
            cx[sel_idx], cy[sel_idx], rx, ry,
            jnp.asarray(malicious[sel_idx]), jnp.asarray(sel_idx), key)
        intra, cross, dollars = wire_bytes(job, sel)
        out.append({"delivered": sel, "intra_bytes": intra,
                    "cross_bytes": cross, "dollars": dollars,
                    "rep": np.asarray(rep),
                    "params": jax.tree.map(np.asarray, params),
                    "res_client_norm": float(_norm(res_client))})
    return out


def draw_malicious(job: Job, seed: int) -> np.ndarray:
    """The static adversary set: a uniform draw of
    floor(malicious_frac * N) clients from ``default_rng(seed)``."""
    rng = np.random.default_rng(seed)
    mal = np.zeros(job.n_clients, bool)
    mal[rng.choice(job.n_clients, int(job.malicious_frac * job.n_clients),
                   replace=False)] = True
    return mal


def poison_labels(job: Job, y: np.ndarray, malicious: np.ndarray,
                  seed: int) -> np.ndarray:
    """label_flip: each malicious client's labels shifted by a uniform
    offset in [1, classes) drawn from ``default_rng(seed + 1)``."""
    y = np.array(y)
    if job.attack != "label_flip":
        return y
    rng = np.random.default_rng(seed + 1)
    for i in np.nonzero(malicious)[0]:
        y[i] = (y[i] + rng.integers(1, job.n_classes, size=y[i].shape)) % job.n_classes
    return y
