"""The paper's three CNN models (§V-A footnotes), in pure JAX.

  * CNN-MNIST : 5x5x10 conv -> 2x2 maxpool -> 5x5x20 conv -> (dropout) ->
                2x2 maxpool -> flatten -> fc 320x50 -> (dropout) -> fc 50x10
  * CNN-FMNIST: 5x5x16 conv -> BN -> 2x2 maxpool -> 5x5x32 conv -> BN ->
                2x2 maxpool -> flatten -> fc 1568x10
  * CNN-CIFAR : 5x5x6 conv -> 2x2 maxpool -> 5x5x16 conv -> flatten ->
                fc 400x120 -> fc 120x84 -> fc 84x10

Dropout is treated as identity at selection/evaluation time (the paper's
selection signal is the *initial gradient*, which it computes in eval-style
passes); batch-norm uses per-batch statistics (no running stats needed for
the FL simulation's short local epochs).

These are the federated local models for the paper-faithful reproduction.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax


def _conv_init(key, shape):  # (kh, kw, cin, cout)
    fan_in = shape[0] * shape[1] * shape[2]
    std = 1.0 / math.sqrt(fan_in)
    return jax.random.uniform(key, shape, jnp.float32, -std, std)


def _fc_init(key, shape):
    std = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, jnp.float32, -std, std)


def conv2d(x, w, b, padding="VALID"):
    """x: (B,H,W,C); w: (kh,kw,cin,cout); VALID or SAME padding.

    XLA's own convolution, at XLA's default precision (on a TPU the
    operands round to bfloat16 and the sums are float32).  Under the
    cohort engine's per-client vmap it becomes a grouped convolution.
    An im2col form (patches @ kernel) was slower on both platforms: on a
    TPU its patch tensor, with 25 or 250 channels on the 128-lane minor
    axis, was a lane-sparse concatenate that took 44 % of a round's
    stage-3 device time, and on a CPU a vmapped SGD step took 1.1-2.4x
    as long (PERF.md §6).
    """
    y = lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


def maxpool2(x):
    """2x2/stride-2 max pool via reshape (gradient avoids the slow
    select-and-scatter path of reduce_window; VALID semantics)."""
    b, h, w, c = x.shape
    if h % 2 or w % 2:
        x = x[:, :h - h % 2, :w - w % 2, :]
        b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max((2, 4))


def batchnorm(x, scale, bias, eps=1e-5):
    mu = x.mean((0, 1, 2), keepdims=True)
    var = x.var((0, 1, 2), keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * scale + bias


# ----------------------------------------------------------------------

def init_cnn(key, variant: str) -> dict:
    ks = jax.random.split(key, 12)
    if variant == "mnist":
        return {
            "c1_w": _conv_init(ks[0], (5, 5, 1, 10)), "c1_b": jnp.zeros(10),
            "c2_w": _conv_init(ks[1], (5, 5, 10, 20)), "c2_b": jnp.zeros(20),
            "f1_w": _fc_init(ks[2], (320, 50)), "f1_b": jnp.zeros(50),
            "f2_w": _fc_init(ks[3], (50, 10)), "f2_b": jnp.zeros(10),
        }
    if variant == "fmnist":
        return {
            "c1_w": _conv_init(ks[0], (5, 5, 1, 16)), "c1_b": jnp.zeros(16),
            "bn1_s": jnp.ones(16), "bn1_b": jnp.zeros(16),
            "c2_w": _conv_init(ks[1], (5, 5, 16, 32)), "c2_b": jnp.zeros(32),
            "bn2_s": jnp.ones(32), "bn2_b": jnp.zeros(32),
            "f1_w": _fc_init(ks[2], (1568, 10)), "f1_b": jnp.zeros(10),
        }
    if variant == "cifar":
        return {
            "c1_w": _conv_init(ks[0], (5, 5, 3, 6)), "c1_b": jnp.zeros(6),
            "c2_w": _conv_init(ks[1], (5, 5, 6, 16)), "c2_b": jnp.zeros(16),
            "f1_w": _fc_init(ks[2], (400, 120)), "f1_b": jnp.zeros(120),
            "f2_w": _fc_init(ks[3], (120, 84)), "f2_b": jnp.zeros(84),
            "f3_w": _fc_init(ks[4], (84, 10)), "f3_b": jnp.zeros(10),
        }
    raise ValueError(variant)


def cnn_logits(params, x, variant: str):
    """x: (B, H, W, C) float32 in [0,1]."""
    p = params
    if variant == "mnist":         # 28x28x1
        h = maxpool2(jax.nn.relu(conv2d(x, p["c1_w"], p["c1_b"])))   # 12
        h = maxpool2(jax.nn.relu(conv2d(h, p["c2_w"], p["c2_b"])))   # 4
        h = h.reshape(h.shape[0], -1)                                 # 320
        h = jax.nn.relu(h @ p["f1_w"] + p["f1_b"])
        return h @ p["f2_w"] + p["f2_b"]
    if variant == "fmnist":        # 28x28x1, SAME padding -> 7x7x32 = 1568
        h = jax.nn.relu(conv2d(x, p["c1_w"], p["c1_b"], "SAME"))
        h = maxpool2(batchnorm(h, p["bn1_s"], p["bn1_b"]))           # 14
        h = jax.nn.relu(conv2d(h, p["c2_w"], p["c2_b"], "SAME"))
        h = maxpool2(batchnorm(h, p["bn2_s"], p["bn2_b"]))           # 7
        h = h.reshape(h.shape[0], -1)                                 # 1568
        return h @ p["f1_w"] + p["f1_b"]
    if variant == "cifar":         # 32x32x3
        h = maxpool2(jax.nn.relu(conv2d(x, p["c1_w"], p["c1_b"])))   # 14
        h = maxpool2(jax.nn.relu(conv2d(h, p["c2_w"], p["c2_b"])))   # 5
        h = h.reshape(h.shape[0], -1)                                 # 400
        h = jax.nn.relu(h @ p["f1_w"] + p["f1_b"])
        h = jax.nn.relu(h @ p["f2_w"] + p["f2_b"])
        return h @ p["f3_w"] + p["f3_b"]
    raise ValueError(variant)


def image_shape(variant: str) -> Tuple[int, int, int]:
    return (32, 32, 3) if variant == "cifar" else (28, 28, 1)


def cnn_nll(params, batch, variant: str):
    """(B,) per-sample negative log-likelihoods."""
    logits = cnn_logits(params, batch["x"], variant)
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, batch["y"][:, None], axis=1)[:, 0]


def cnn_loss(params, batch, variant: str):
    return cnn_nll(params, batch, variant).mean()


def cnn_accuracy(params, batch, variant: str):
    logits = cnn_logits(params, batch["x"], variant)
    return (logits.argmax(-1) == batch["y"]).mean()


cnn_grad = jax.jit(jax.grad(cnn_loss), static_argnames="variant")
