"""Client-side substrate for the simulation-scale reproduction:
the paper's CNN (2 conv + 2 FC, §V-A) and vmap-able local training
(LocalTrain in Algorithm 1, line 8)."""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array
Params = Dict[str, Array]


def cnn_init(key: Array, input_shape: Tuple[int, int, int],
             n_classes: int) -> Params:
    h, w, c = input_shape
    ks = jax.random.split(key, 4)
    hh, ww = h // 4, w // 4                      # two 2x2 pools
    flat = hh * ww * 64

    def norm(k, shape, fan_in):
        return jax.random.normal(k, shape) * jnp.sqrt(2.0 / fan_in)

    return {
        "conv1_w": norm(ks[0], (3, 3, c, 32), 9 * c),
        "conv1_b": jnp.zeros((32,)),
        "conv2_w": norm(ks[1], (3, 3, 32, 64), 9 * 32),
        "conv2_b": jnp.zeros((64,)),
        "fc1_w": norm(ks[2], (flat, 128), flat),
        "fc1_b": jnp.zeros((128,)),
        "fc2_w": norm(ks[3], (128, n_classes), 128),
        "fc2_b": jnp.zeros((n_classes,)),
    }


def _conv_im2col(x: Array, w: Array) -> Array:
    """3x3 SAME convolution as one GEMM over the nine shifted patches."""
    _, h, ww, c = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    patches = jnp.concatenate(
        [xp[:, i:i + h, j:j + ww, :] for i in range(3) for j in range(3)],
        axis=-1)                                          # (B,H,W,9C)
    return patches @ w.reshape(9 * c, -1)


def _conv_native(x: Array, w: Array) -> Array:
    """3x3 SAME convolution as XLA's own convolution op."""
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _conv(x: Array, w: Array) -> Array:
    # im2col lane-pads its patches on TPU; XLA:CPU's direct conv is 16x slower
    return jax.lax.platform_dependent(x, w, cpu=_conv_im2col,
                                      tpu=_conv_native)


def cnn_apply(params: Params, x: Array) -> Array:
    """x: (B, H, W, C) -> logits (B, n_classes)."""
    def conv(x, w, b):
        return jax.nn.relu(_conv(x, w) + b)

    def pool(x):
        # the reference's reshape pool; reduce_window's grad: 6x slower (CPU)
        b, h, w, c = x.shape
        return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))

    x = pool(conv(x, params["conv1_w"], params["conv1_b"]))
    x = pool(conv(x, params["conv2_w"], params["conv2_b"]))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["fc1_w"] + params["fc1_b"])
    return x @ params["fc2_w"] + params["fc2_b"]


def xent_loss(params: Params, x: Array, y: Array) -> Array:
    logits = cnn_apply(params, x)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def accuracy(params: Params, x: Array, y: Array, batch: int = 512) -> float:
    correct = 0
    for i in range(0, x.shape[0], batch):
        logits = cnn_apply(params, x[i:i + batch])
        correct += int(jnp.sum(jnp.argmax(logits, -1) == y[i:i + batch]))
    return correct / x.shape[0]


@partial(jax.jit, static_argnames=("epochs", "batch"))
def local_train(params: Params, x: Array, y: Array, key: Array, *,
                epochs: int, batch: int, lr: float) -> Params:
    """E epochs of minibatch SGD from the broadcast global params.
    Returns the *update* g_i = w_global - w_local (so that
    w <- w - eta * g descends toward the client optimum).
    vmap-able over a leading client axis."""
    n = x.shape[0]
    steps_per_epoch = max(1, n // batch)
    total = epochs * steps_per_epoch

    def step(p, k):
        ix = jax.random.randint(k, (batch,), 0, n)
        g = jax.grad(xent_loss)(p, x[ix], y[ix])
        p = jax.tree.map(lambda w, gw: w - lr * gw, p, g)
        return p, None

    local, _ = jax.lax.scan(step, params, jax.random.split(key, total))
    return jax.tree.map(lambda g0, g1: g0 - g1, params, local)
