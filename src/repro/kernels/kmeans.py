"""Pallas TPU kernels for fleet-scale k-means (stage-1 clustering).

The paper's stage-1 clusters N clients by gradient features; at fleet scale
(N ~ 1e5-1e6 clients, F = 256-4096 features) every Lloyd iteration is the
compute hotspot. Two kernels:

  * :func:`kmeans_assign` — assignment only (pairwise distance + argmin).
  * :func:`lloyd_step`    — the fused assign+update step: one grid pass
    over N emits labels and min-distances per tile AND accumulates the
    per-centroid partial sums / counts, so a full Lloyd iteration needs no
    separate (N, K) one-hot matmul over a second pass of the features.

TPU mapping (both kernels):

  * grid over blocks of N; each step loads an (BN, F) tile of features into
    VMEM (BlockSpec), with the full (K, F) centroid matrix resident (K is
    small: the paper uses J=10 clusters; padded to 128 rows);
  * distances via the MXU:  ||x-c||^2 = ||x||^2 - 2 c·x^T + ||c||^2 — the
    c·x^T term is a (Kp, F) @ (F, BN) matmul at f32 precision, so clients
    sit on lanes and centroids on sublanes;
  * argmin + min-distance reduce over sublanes into (1, BN) rows, written
    as lane-dense slices of a (1, Npad) output (a 1-D (BN,) block does not
    match the TPU's tiling of an (Npad,) array and is refused by Mosaic);
  * (lloyd_step) the tile's one-hot @ x partial sums and counts are
    accumulated into a (Kp, F) / (Kp, 1) output block that every grid step
    maps to — zeroed at step 0, so the sequential TPU grid acts as the
    reduction loop.

VMEM: at the default BN=128 the double-buffered feature tile, resident
centroids and (lloyd_step) sum accumulator fit v5e's default scoped VMEM
for F <= 4096 in f32 (tests/test_tpu_compile.py compiles F = 256 and 4096
for a described v5e); lloyd_step at F = 8192 runs out of VMEM. Stage 1
projects wider features to ``cluster_feature_dim`` first.

The kernels compile for the TPU by default; ``interpret=True`` runs them
under the Pallas interpreter (the CPU tests, against
ref.kmeans_assign_ref / ref.lloyd_step_ref). Platform dispatch lives in
repro.kernels.ops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _distances(x, c, k_real):
    """(Kp, BN) squared distances, centroids on sublanes and clients on
    lanes, padded centroid rows = +inf.  The client axis on lanes is what
    makes the per-client outputs lane-dense (1, BN) rows."""
    c = c.astype(jnp.float32)
    x = x.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    prod = jax.lax.dot_general(
        c, x, (((1,), (1,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)       # (Kp, BN) on the MXU
    # ||x||^2 as a (1, BN) row: a ones-row matmul puts the client axis on
    # lanes without a sublane->lane relayout
    ones = jnp.ones((8, x.shape[1]), jnp.float32)
    xn = jax.lax.dot_general(
        ones, x * x, (((1,), (1,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)[:1]   # (1, BN)
    cn = jnp.sum(c * c, axis=1, keepdims=True)    # (Kp, 1)
    d = xn - 2.0 * prod + cn                      # (Kp, BN)
    row = jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)
    return jnp.where(row < k_real, d, jnp.inf), row


def _argmin_rows(d, row):
    """First-index argmin over the centroid (sublane) axis -> (1, BN)."""
    dmin = jnp.min(d, axis=0, keepdims=True)
    lab = jnp.min(jnp.where(d == dmin, row, d.shape[0]), axis=0,
                  keepdims=True)
    return lab, dmin


def _assign_kernel(x_ref, c_ref, lab_ref, dist_ref, *, k_real: int):
    d, row = _distances(x_ref[...], c_ref[...], k_real)
    lab, dmin = _argmin_rows(d, row)
    lab_ref[...] = lab
    dist_ref[...] = dmin


def _lloyd_kernel(x_ref, c_ref, lab_ref, dist_ref, sum_ref, cnt_ref,
                  *, k_real: int, n_real: int, block_n: int):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)            # (BN, F)
    d, row = _distances(x, c_ref[...], k_real)
    lab, dmin = _argmin_rows(d, row)              # (1, BN) each
    lane = jax.lax.broadcasted_iota(jnp.int32, lab.shape, 1)
    valid = lane + i * block_n < n_real           # padded clients masked
    lab_ref[...] = lab
    dist_ref[...] = jnp.where(valid, dmin, 0.0)
    onehot = ((row == lab) & valid).astype(jnp.float32)   # (Kp, BN)
    # partial assign+update: every grid step maps to the same (Kp, F) /
    # (Kp, 1) output block, so += across the sequential grid reduces N
    @pl.when(i == 0)
    def _():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
    sum_ref[...] += jax.lax.dot_general(
        onehot, x, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)       # (Kp, F) = onehot @ x
    cnt_ref[...] += jnp.sum(onehot, axis=1, keepdims=True)


def _pad_to(x, m, axis, value=0.0):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    cfgp = [(0, 0)] * x.ndim
    cfgp[axis] = (0, pad)
    return jnp.pad(x, cfgp, constant_values=value)


def _padded(x, c, block_n):
    return (_pad_to(_pad_to(x, block_n, 0), 128, 1),
            _pad_to(_pad_to(c, 128, 0), 128, 1))


def _specs(block_n, kp, fp):
    """Feature tile per grid step, centroids resident, per-client outputs
    as lane-dense (1, block_n) slices of a (1, Npad) row."""
    ins = [pl.BlockSpec((block_n, fp), lambda i: (i, 0)),
           pl.BlockSpec((kp, fp), lambda i: (0, 0))]
    row = pl.BlockSpec((1, block_n), lambda i: (0, i))
    return ins, [row, row]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign(x: jnp.ndarray, c: jnp.ndarray, *, block_n: int = 128,
                  interpret: bool = False):
    """x: (N, F), c: (K, F) -> (labels (N,) int32, min_dist (N,) f32)."""
    n, _ = x.shape
    k = c.shape[0]
    xp, cp = _padded(x, c, block_n)
    kp = cp.shape[0]
    npad, fp = xp.shape
    in_specs, out_specs = _specs(block_n, kp, fp)
    labels, dists = pl.pallas_call(
        functools.partial(_assign_kernel, k_real=k),
        grid=(npad // block_n,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((1, npad), jnp.int32),
            jax.ShapeDtypeStruct((1, npad), jnp.float32),
        ],
        interpret=interpret,
    )(xp, cp)
    return labels[0, :n], dists[0, :n]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def lloyd_step(x: jnp.ndarray, c: jnp.ndarray, *, block_n: int = 128,
               interpret: bool = False):
    """Fused Lloyd assign+update. x: (N, F), c: (K, F) ->
    (labels (N,) int32, min_dist (N,) f32, sums (K, F) f32, counts (K,) f32)
    where sums[k] = sum of features assigned to k and counts[k] their count
    — one grid pass over N, no second (N, K) one-hot matmul."""
    n, f = x.shape
    k = c.shape[0]
    xp, cp = _padded(x, c, block_n)
    kp = cp.shape[0]
    npad, fp = xp.shape
    in_specs, out_specs = _specs(block_n, kp, fp)
    labels, dists, sums, counts = pl.pallas_call(
        functools.partial(_lloyd_kernel, k_real=k, n_real=n,
                          block_n=block_n),
        grid=(npad // block_n,),
        in_specs=in_specs,
        out_specs=out_specs + [
            pl.BlockSpec((kp, fp), lambda i: (0, 0)),        # accumulators
            pl.BlockSpec((kp, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, npad), jnp.int32),
            jax.ShapeDtypeStruct((1, npad), jnp.float32),
            jax.ShapeDtypeStruct((kp, fp), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xp, cp)
    return labels[0, :n], dists[0, :n], sums[:k, :f], counts[:k, 0]
