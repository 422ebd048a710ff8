"""Plain reference of CNN-MNIST, the paper's model (arXiv:2103.07150 §V-A;
the MNIST CNN of McMahan et al. 2017 with the paper's widths):

    5x5 conv 1->10, ReLU, 2x2 max pool,      28x28x1 -> 12x12x10
    5x5 conv 10->20, ReLU, 2x2 max pool,     -> 4x4x20, flattened to 320
    fc 320->50, ReLU, fc 50->10 logits;      loss: mean cross-entropy.

Written from that description with ``lax.conv_general_dilated`` and
``lax.reduce_window``; dropout is left out, as in the program.

Precision: ``dtype`` is the type of weights, activations and updates, and
``operands`` the type every convolution and dense product rounds its two
operands to before multiplying them exactly and summing in ``dtype`` (in
the forward pass and in both products of its gradient).  That is XLA's
default matmul precision (bfloat16 operands on a TPU, float32 on a CPU);
a configuration states which it runs at.  ``dtype=bfloat16`` is the
control of the correctness check.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

SHAPES = {"c1_w": (5, 5, 1, 10), "c1_b": (10,),
          "c2_w": (5, 5, 10, 20), "c2_b": (20,),
          "f1_w": (320, 50), "f1_b": (50,),
          "f2_w": (50, 10), "f2_b": (10,)}
HIGHEST = lax.Precision.HIGHEST


@jax.jit
def init(key) -> dict:
    """Weights uniform in +-1/sqrt(fan_in), biases zero, in float32: one
    jitted call on the device."""
    keys = jax.random.split(key, 4)
    out = {}
    for k, name in zip(keys, ("c1_w", "c2_w", "f1_w", "f2_w")):
        shape = SHAPES[name]
        bound = 1.0 / math.sqrt(math.prod(shape[:-1]))
        out[name] = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        bname = name[:-1] + "b"
        out[bname] = jnp.zeros(SHAPES[bname], jnp.float32)
    return out


def _conv(x, w):
    return lax.conv_general_dilated(x, w, (1, 1), "VALID",
                                    dimension_numbers=("NHWC", "HWIO",
                                                       "NHWC"),
                                    precision=HIGHEST)


def _dot(a, b):
    return jnp.dot(a, b, precision=HIGHEST)


def _round(a, operands):
    return a.astype(operands).astype(a.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def _product(f, a, b, operands):
    return f(_round(a, operands), _round(b, operands))


def _product_fwd(f, a, b, operands):
    ra, rb = _round(a, operands), _round(b, operands)
    return f(ra, rb), (ra, rb)


def _product_bwd(f, operands, res, g):
    _, vjp = jax.vjp(f, *res)
    return vjp(_round(g, operands))


_product.defvjp(_product_fwd, _product_bwd)


def _pool(x):
    return lax.reduce_window(x, -jnp.inf, lax.max,
                             (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def logits(p, x, operands):
    h = _pool(jax.nn.relu(_product(_conv, x, p["c1_w"], operands)
                          + p["c1_b"]))
    h = _pool(jax.nn.relu(_product(_conv, h, p["c2_w"], operands)
                          + p["c2_b"]))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(_product(_dot, h, p["f1_w"], operands) + p["f1_b"])
    return _product(_dot, h, p["f2_w"], operands) + p["f2_b"]


def loss(p, x, y, operands):
    logp = jax.nn.log_softmax(logits(p, x, operands))
    return -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0].mean()


def _cast(p, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), p)


@partial(jax.jit, static_argnames=("dtype", "operands"))
def sgd_step(p, x, y, lr, dtype, operands):
    """One plain SGD step on one minibatch."""
    p = _cast(p, dtype)
    g = jax.grad(loss)(p, x.astype(dtype), y, operands)
    return jax.tree.map(lambda a, b: a - jnp.asarray(lr, dtype) * b, p, g)


@partial(jax.jit, static_argnames="operands")
def forward(p, x, operands):
    """Float32 logits with the products' operands rounded to
    ``operands``."""
    return logits(_cast(p, jnp.float32), x.astype(jnp.float32), operands)


@partial(jax.jit, static_argnames=("dtype", "operands"))
def eval_loss(p, x, y, dtype, operands):
    return loss(_cast(p, dtype), x.astype(dtype), y,
                operands).astype(jnp.float32)
