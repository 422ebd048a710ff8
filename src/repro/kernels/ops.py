"""jit'd public wrappers for the Pallas kernels with platform dispatch:
compiled Pallas on TPU, the Pallas interpreter or a jnp formulation on
other backends, and the pure-jnp references on request (``impl="ref"``).
This is the one place that asks which backend runs; the kernels
themselves compile for the TPU unless told to interpret.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref as REF
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.kmeans import kmeans_assign as _kmeans_pallas
from repro.kernels.kmeans import lloyd_step as _lloyd_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kmeans_assign(x, c, *, impl: str = "auto"):
    """Returns labels (N,) int32. impl: auto | pallas | ref."""
    if impl == "ref" or (impl == "auto" and x.shape[0] > 100_000
                         and not _on_tpu()):
        # interpret-mode pallas is slow for very large N on CPU
        return REF.kmeans_assign_ref(x, c)
    labels, _ = _kmeans_pallas(x, c, interpret=not _on_tpu())
    return labels


def _lloyd_step_jnp(x, c):
    """Fused Lloyd step without Pallas: the same MXU-friendly matmul
    decomposition (||x||^2 - 2 x.c^T + ||c||^2 distances, one-hot^T @ x
    update) as XLA ops — the fast off-TPU path, and vmap/scan-safe."""
    x32 = x.astype(jnp.float32)
    c32 = c.astype(jnp.float32)
    d = ((x32 * x32).sum(1, keepdims=True) - 2.0 * (x32 @ c32.T)
         + (c32 * c32).sum(1)[None, :])
    lab = jnp.argmin(d, axis=1).astype(jnp.int32)
    onehot = jax.nn.one_hot(lab, c.shape[0], dtype=jnp.float32)
    return lab, d.min(axis=1), onehot.T @ x32, onehot.sum(0)


def lloyd_step(x, c, *, impl: str = "auto"):
    """One fused Lloyd assign+update pass. Returns (labels (N,) int32,
    min_dist (N,) f32, sums (K, F) f32, counts (K,) f32).

    impl: auto — compiled Pallas on TPU, fused jnp elsewhere (interpret
    mode pays a per-tile interpreter cost that defeats the fusion on CPU);
    pallas — force the kernel (interpreted off TPU); ref — the naive
    (N, K, F)-broadcast oracle."""
    if impl == "ref":
        return REF.lloyd_step_ref(x, c)
    if impl == "pallas" or (impl == "auto" and _on_tpu()):
        return _lloyd_pallas(x, c, interpret=not _on_tpu())
    return _lloyd_step_jnp(x, c)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    impl: str = "auto"):
    if impl == "ref":
        return REF.flash_attention_ref(q, k, v, causal=causal, window=window)
    return _flash_pallas(q, k, v, causal=causal, window=window,
                         interpret=not _on_tpu())
