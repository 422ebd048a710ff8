"""Gradient-based client clustering (paper §III-C).

Stage-1 of Algorithm 1:
  1. every client draws ``s_mm`` samples from its local data (the *sample
     window* — the imbalance fix: every client contributes equally many
     samples to its clustering feature), repeats ``T0`` times, and averages
     the gradient of the *initial* global model over the draws;
  2. the server k-means-clusters the gradient features into J groups.

For LLM-scale models the full gradient is too large to ship; we use a fixed
random projection of the concatenated (last-block, lm-head) gradient to
``feature_dim`` — applied in column blocks so the (in_dim, feature_dim)
Gaussian is never materialized whole (DESIGN.md, fleet-scale adaptation).
For the paper's CNNs the full flattened gradient fits and is used directly.

K-means runs through a fully-jitted engine (:func:`kmeans`): incremental
k-means++ seeding (distance only to the newest centroid per pick), all
``restarts`` Lloyd runs vmapped inside one compiled program, and the fused
assign+update step dispatched per backend (Pallas kernel on TPU, the same
matmul decomposition as XLA ops elsewhere — repro.kernels.ops.lloyd_step).
The seed implementation is kept verbatim as :func:`kmeans_reference`, the
run-for-run oracle and benchmark baseline.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import FLConfig
from repro.kernels import ops as KOPS


# ----------------------------------------------------------------------
# gradient features
# ----------------------------------------------------------------------

def window_indices(key, local_size: int, window: int) -> jnp.ndarray:
    """Sample-window draw: `window` indices from [0, local_size) (with
    replacement if the client has fewer samples than the window)."""
    return jax.random.randint(key, (window,), 0, local_size)


def client_gradient_feature(grad_fn: Callable, params, data_x, data_y,
                            local_size: int, cfg: FLConfig, key,
                            flatten: bool = True) -> jnp.ndarray:
    """Mean gradient of the initial model over T0 sample-window draws."""
    feats = []
    for t in range(cfg.cluster_resamples):
        k = jax.random.fold_in(key, t)
        idx = window_indices(k, local_size, cfg.sample_window)
        g = grad_fn(params, {"x": data_x[idx], "y": data_y[idx]})
        feats.append(g)
    mean_g = jax.tree.map(lambda *xs: sum(xs) / len(xs), *feats)
    if not flatten:
        return mean_g
    leaves = [x.reshape(-1) for x in jax.tree.leaves(mean_g)]
    return jnp.concatenate(leaves)


def random_projection(key, in_dim: int, out_dim: int) -> jnp.ndarray:
    """Fixed Gaussian projection (Johnson-Lindenstrauss) for LLM gradients.

    Materializes the full (in_dim, out_dim) matrix — fine for tests and
    small models; the fleet-scale path is :func:`project_features_blocked`,
    which never holds more than one column block of it."""
    return jax.random.normal(key, (in_dim, out_dim)) / jnp.sqrt(out_dim)


def project_feature(feat: jnp.ndarray, proj: Optional[jnp.ndarray]):
    return feat if proj is None else feat @ proj


@partial(jax.jit, static_argnames=("out_dim", "block"))
def project_features_blocked(key, feats: jnp.ndarray, out_dim: int,
                             block: int = 4096) -> jnp.ndarray:
    """JL projection of (N, in_dim) features to (N, out_dim) in column
    blocks: each scan step draws one (block, out_dim) Gaussian slab keyed
    on the block index and accumulates ``feats[:, b] @ G_b``, so peak
    memory is O(N·out_dim + block·out_dim) — the (in_dim, out_dim) matrix
    (100s of GB at LLM gradient widths) is never materialized."""
    n, in_dim = feats.shape
    nb = -(-in_dim // block)
    pad = nb * block - in_dim
    fp = jnp.pad(feats.astype(jnp.float32), ((0, 0), (0, pad)))
    fb = fp.reshape(n, nb, block).transpose(1, 0, 2)        # (nb, N, block)

    def body(acc, inp):
        b, xb = inp
        g = jax.random.normal(jax.random.fold_in(key, b), (block, out_dim))
        return acc + xb @ g, None

    acc, _ = jax.lax.scan(body, jnp.zeros((n, out_dim), jnp.float32),
                          (jnp.arange(nb), fb))
    return acc / jnp.sqrt(out_dim)


# ----------------------------------------------------------------------
# k-means
# ----------------------------------------------------------------------

def assign_ref(x: jnp.ndarray, centroids: jnp.ndarray) -> jnp.ndarray:
    """Pure-jnp oracle for the Pallas kmeans kernel: argmin_k ||x - c_k||²."""
    d = ((x[:, None, :] - centroids[None, :, :]) ** 2).sum(-1)
    return jnp.argmin(d, axis=1)


def _kmeanspp_init_scan(features, k, key):
    """The seed k-means++ — kept as the seeding oracle: every pick
    recomputes the distance to *all* chosen centroids through an
    (N, K, F) broadcast (O(N·K·F) time and memory per pick)."""
    n = features.shape[0]
    k0, key = jax.random.split(key)
    first = jax.random.randint(k0, (), 0, n)
    cent0 = jnp.tile(features[first][None], (k, 1))

    def pick(carry, i):
        cent, key = carry
        d = ((features[:, None, :] - cent[None]) ** 2).sum(-1)
        col = jnp.arange(k)[None, :]
        d = jnp.where(col < i, d, jnp.inf)
        dmin = d.min(axis=1)
        key, kp = jax.random.split(key)
        p = dmin / jnp.maximum(dmin.sum(), 1e-30)
        nxt = jax.random.choice(kp, n, p=p)
        cent = cent.at[i].set(features[nxt])
        return (cent, key), None

    (cent, _), _ = jax.lax.scan(pick, (cent0, key), jnp.arange(1, k))
    return cent


def _kmeanspp_init(features, k, key):
    """Incremental k-means++: a running min-distance vector is updated
    with the distance to the *newest* centroid only — O(N·F) time and O(N)
    state per pick, no (N, K, F) intermediate. Key stream and per-centroid
    distance math match :func:`_kmeanspp_init_scan` term for term, so the
    picked seeds are identical (tested)."""
    n = features.shape[0]
    k0, key = jax.random.split(key)
    first = jax.random.randint(k0, (), 0, n)
    c0 = features[first]
    cent0 = jnp.tile(c0[None], (k, 1))
    dmin0 = ((features - c0[None]) ** 2).sum(-1)

    def pick(carry, i):
        cent, dmin, key = carry
        key, kp = jax.random.split(key)
        p = dmin / jnp.maximum(dmin.sum(), 1e-30)
        nxt = jax.random.choice(kp, n, p=p)
        cnew = features[nxt]
        cent = cent.at[i].set(cnew)
        dmin = jnp.minimum(dmin, ((features - cnew[None]) ** 2).sum(-1))
        return (cent, dmin, key), None

    (cent, _, _), _ = jax.lax.scan(pick, (cent0, dmin0, key),
                                   jnp.arange(1, k))
    return cent


@partial(jax.jit,
         static_argnames=("k", "iters", "restarts", "assign_fn", "impl"))
def _kmeans_batched(features, key, *, k: int, iters: int, restarts: int,
                    assign_fn, impl: str):
    """One compiled program for the whole stage: incremental k-means++
    seeding, Lloyd iterations, and the restart-argmin — all ``restarts``
    runs vmapped, no Python loop and no per-restart host sync."""
    obs.jax_stats.note_trace("kmeans")   # fires at (re)trace time only
    n = features.shape[0]
    feats32 = features.astype(jnp.float32)

    def update(cent):
        if assign_fn is not None:
            # external assignment (e.g. the Pallas assign kernel under
            # test) — centroid update stays the one-hot matmul
            lab = assign_fn(features, cent)
            onehot = jax.nn.one_hot(lab, k, dtype=jnp.float32)
            counts = onehot.sum(0)
            sums = onehot.T @ feats32
        else:
            lab, _, sums, counts = KOPS.lloyd_step(features, cent, impl=impl)
        new = jnp.where(counts[:, None] > 0,
                        sums / jnp.maximum(counts[:, None], 1.0), cent)
        return new.astype(features.dtype), lab

    def one_run(kr):
        cent = _kmeanspp_init(features, k, kr)
        cent, _ = jax.lax.scan(lambda c, _: (update(c)[0], None), cent,
                               None, length=iters)
        if assign_fn is not None:
            lab = assign_fn(features, cent)
            inertia = ((feats32 - cent[lab].astype(jnp.float32)) ** 2).sum()
        else:
            lab, dist, _, _ = KOPS.lloyd_step(features, cent, impl=impl)
            inertia = dist.sum()
        return lab.astype(jnp.int32), cent, inertia

    keys = jax.vmap(lambda r: jax.random.fold_in(key, r))(
        jnp.arange(restarts))
    labs, cents, inertias = jax.vmap(one_run)(keys)
    best = jnp.argmin(inertias)      # first index on ties, like the oracle
    return labs[best], cents[best]


def kmeans(features: jnp.ndarray, k: int, key, iters: int = 25,
           assign_fn: Callable = None, restarts: int = 4,
           impl: str = "auto") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Lloyd's algorithm with k-means++ seeding and best-of-``restarts``
    (by inertia). features: (N, F). Returns (labels (N,), centroids (k,F)).

    Fully jitted: seeding + Lloyd + restart-argmin run as one compiled
    program (see :func:`_kmeans_batched`). ``impl`` selects the fused
    assign+update backend (repro.kernels.ops.lloyd_step: auto | pallas |
    ref); ``assign_fn`` overrides assignment only (testing hook)."""
    return _kmeans_batched(features, key, k=k, iters=iters,
                           restarts=restarts, assign_fn=assign_fn,
                           impl=impl)


def kmeans_reference(features: jnp.ndarray, k: int, key, iters: int = 25,
                     restarts: int = 4) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The seed implementation, kept verbatim as the run-for-run oracle and
    benchmark baseline: Python loop over restarts with a ``float(inertia)``
    host sync each, (N, K, F)-broadcast seeding and assignment, separate
    one-hot matmul update. Same per-restart key stream (fold_in) as
    :func:`kmeans`."""
    def one_run(key):
        cent = _kmeanspp_init_scan(features, k, key)

        def step(cent, _):
            lab = assign_ref(features, cent)
            onehot = jax.nn.one_hot(lab, k, dtype=features.dtype)  # (N, k)
            counts = onehot.sum(0)
            sums = onehot.T @ features
            new = jnp.where(counts[:, None] > 0,
                            sums / jnp.maximum(counts[:, None], 1.0), cent)
            return new, None

        cent, _ = jax.lax.scan(step, cent, None, length=iters)
        lab = assign_ref(features, cent)
        inertia = ((features - cent[lab]) ** 2).sum()
        return lab, cent, inertia

    best = None
    for r in range(restarts):
        lab, cent, inertia = one_run(jax.random.fold_in(key, r))
        if best is None or float(inertia) < best[2]:
            best = (lab, cent, float(inertia))
    return best[0], best[1]


# ----------------------------------------------------------------------
# full clustering stage (Algorithm 1, lines 1-8)
# ----------------------------------------------------------------------

def cluster_clients(grad_fn: Callable, params, client_data, cfg: FLConfig,
                    key, feature_kind: str = "gradient",
                    local_steps_fn: Callable = None,
                    assign_fn: Callable = None,
                    precomputed_feats: Optional[jnp.ndarray] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Cluster all clients. client_data: list of (x, y) arrays per client.

    feature_kind:
      * 'gradient' — the paper's scheme (sample window + T0 mean gradients)
      * 'weights'  — the Wang et al. [2] baseline: feature = local model
        delta after one epoch of SGD (needs local_steps_fn).

    ``precomputed_feats`` (N, D) bypasses the per-client feature loop —
    the repro.sim device runtime computes the same features as one
    batched program; projection and k-means still run here so both paths
    share one code path from raw features onward.

    K-means runs through the jitted batched-restart engine (the Pallas
    fused Lloyd step on TPU, its jnp twin elsewhere); oversized features
    are JL-projected in column blocks first.

    Returns (labels (N,), centroids, features).
    """
    n = cfg.num_clients
    if precomputed_feats is not None:
        feats = precomputed_feats
    else:
        with obs.span("cluster/features", feature=feature_kind,
                      runtime="reference"):
            feats = []
            for i in range(n):
                x, y = client_data[i]
                ki = jax.random.fold_in(key, i)
                if feature_kind == "gradient":
                    f = client_gradient_feature(grad_fn, params, x, y,
                                                x.shape[0], cfg, ki)
                else:
                    f = local_steps_fn(params, x, y, ki)
                feats.append(f)
            feats = jnp.stack(feats)
    if feats.shape[1] > cfg.cluster_feature_dim * 8:
        with obs.span("cluster/project", dim=int(feats.shape[1])):
            feats = project_features_blocked(jax.random.PRNGKey(1234),
                                             feats,
                                             cfg.cluster_feature_dim)
    with obs.span("cluster/kmeans", k=cfg.num_clusters):
        labels, cent = kmeans(feats, cfg.num_clusters, key,
                              assign_fn=assign_fn)
    return labels, cent, feats
