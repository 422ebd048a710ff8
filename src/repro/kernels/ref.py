"""Pure-jnp oracles for the Pallas kernels (per-kernel allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def kmeans_assign_ref(x: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """x: (N, F), c: (K, F) -> argmin_k ||x_n - c_k||^2, int32 (N,)."""
    d = (x[:, None, :].astype(jnp.float32)
         - c[None, :, :].astype(jnp.float32)) ** 2
    return jnp.argmin(d.sum(-1), axis=1).astype(jnp.int32)


def kmeans_min_dist_ref(x: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    d = ((x[:, None, :].astype(jnp.float32)
          - c[None, :, :].astype(jnp.float32)) ** 2).sum(-1)
    return d.min(axis=1)


def lloyd_step_ref(x: jnp.ndarray, c: jnp.ndarray):
    """Oracle for the fused Lloyd assign+update kernel. x: (N, F),
    c: (K, F) -> (labels (N,) int32, min_dist (N,) f32, sums (K, F) f32,
    counts (K,) f32) with sums[k] = sum of rows assigned to centroid k."""
    x32 = x.astype(jnp.float32)
    c32 = c.astype(jnp.float32)
    d = ((x32[:, None, :] - c32[None, :, :]) ** 2).sum(-1)
    lab = jnp.argmin(d, axis=1).astype(jnp.int32)
    onehot = jax.nn.one_hot(lab, c.shape[0], dtype=jnp.float32)
    # f32 on every backend (the TPU's default would round x32 to bf16)
    sums = jnp.matmul(onehot.T, x32, precision=jax.lax.Precision.HIGHEST)
    return lab, d.min(axis=1), sums, onehot.sum(0)


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: int = 0) -> jnp.ndarray:
    """q,k,v: (B, S, H, hd) (kv already expanded to H heads)."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w,
                      v.astype(jnp.float32)).astype(q.dtype)
