"""Per-kernel allclose tests: shape/dtype sweeps against the ref.py oracles,
executed in Pallas interpret mode on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.kmeans import kmeans_assign, lloyd_step
from repro.kernels.flash_attention import flash_attention


KEY = jax.random.PRNGKey(7)


@pytest.mark.parametrize("n,f,k", [
    (16, 8, 2), (100, 64, 10), (257, 256, 7), (512, 100, 16), (33, 33, 3),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kmeans_assign_matches_ref(n, f, k, dtype):
    kx, kc = jax.random.split(jax.random.fold_in(KEY, n * f + k))
    x = jax.random.normal(kx, (n, f), dtype=dtype)
    c = jax.random.normal(kc, (k, f), dtype=dtype)
    lab, dist = kmeans_assign(x, c, interpret=True)
    lab_ref = ref.kmeans_assign_ref(x, c)
    dist_ref = ref.kmeans_min_dist_ref(x, c)
    # bf16 rounding can flip near-ties; require distance-consistency instead
    # of exact label match in that case.
    if dtype == jnp.float32:
        np.testing.assert_array_equal(np.asarray(lab), np.asarray(lab_ref))
    tol = 1e-4 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(dist), np.asarray(dist_ref),
                               rtol=tol, atol=tol)


def test_kmeans_assign_backend_probe_default():
    """The kernel compiles for the TPU unless told to interpret: off the
    TPU its default must raise rather than fall back to the interpreter,
    and the platform probe lives in ops (whose default agrees with the
    oracle here)."""
    kx, kc = jax.random.split(KEY)
    x = jax.random.normal(kx, (130, 48))
    c = jax.random.normal(kc, (5, 48))
    assert jax.default_backend() != "tpu"
    with pytest.raises(Exception, match="(?i)interpret"):
        kmeans_assign(x, c)                  # no interpret argument
    lab = ops.kmeans_assign(x, c, impl="pallas")
    np.testing.assert_array_equal(np.asarray(lab),
                                  np.asarray(ref.kmeans_assign_ref(x, c)))
    _, dist = kmeans_assign(x, c, interpret=True)
    np.testing.assert_allclose(np.asarray(dist),
                               np.asarray(ref.kmeans_min_dist_ref(x, c)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("impl", ["auto", "pallas", "ref"])
def test_ops_kmeans_assign_impls_agree(impl):
    kx, kc = jax.random.split(jax.random.fold_in(KEY, 3))
    x = jax.random.normal(kx, (200, 32))
    c = jax.random.normal(kc, (6, 32))
    np.testing.assert_array_equal(
        np.asarray(ops.kmeans_assign(x, c, impl=impl)),
        np.asarray(ref.kmeans_assign_ref(x, c)))


@pytest.mark.parametrize("n,f,k", [
    # unpadded (multiples of the 128-lane tiles) and padded N, F and K
    (256, 128, 8), (16, 8, 2), (100, 64, 10), (257, 256, 7), (130, 100, 16),
    (33, 33, 3),
])
def test_lloyd_step_matches_ref(n, f, k):
    """Fused assign+update kernel: labels, min-distances, per-centroid
    partial sums and counts all match the oracle (padded rows masked)."""
    kx, kc = jax.random.split(jax.random.fold_in(KEY, n * f + k))
    x = jax.random.normal(kx, (n, f))
    c = jax.random.normal(kc, (k, f))
    lab, dist, sums, counts = lloyd_step(x, c, interpret=True)
    lab_r, dist_r, sums_r, counts_r = ref.lloyd_step_ref(x, c)
    np.testing.assert_array_equal(np.asarray(lab), np.asarray(lab_r))
    np.testing.assert_allclose(np.asarray(dist), np.asarray(dist_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(sums), np.asarray(sums_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_r))
    assert int(counts.sum()) == n            # padding contributes nothing


@pytest.mark.parametrize("impl", ["auto", "pallas", "ref"])
def test_ops_lloyd_step_impls_agree(impl):
    kx, kc = jax.random.split(jax.random.fold_in(KEY, 11))
    x = jax.random.normal(kx, (150, 40))
    c = jax.random.normal(kc, (5, 40))
    lab, dist, sums, counts = ops.lloyd_step(x, c, impl=impl)
    lab_r, dist_r, sums_r, counts_r = ref.lloyd_step_ref(x, c)
    np.testing.assert_array_equal(np.asarray(lab), np.asarray(lab_r))
    np.testing.assert_allclose(np.asarray(dist), np.asarray(dist_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(sums), np.asarray(sums_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_r))


@pytest.mark.parametrize("b,s,h,hd", [
    (1, 64, 1, 16), (2, 128, 4, 64), (1, 200, 2, 32), (2, 96, 3, 8),
])
@pytest.mark.parametrize("causal,window", [
    (True, 0), (False, 0), (True, 32),
])
def test_flash_attention_matches_ref(b, s, h, hd, causal, window):
    ks = jax.random.split(jax.random.fold_in(KEY, s * h), 3)
    q, k, v = (jax.random.normal(kk, (b, s, h, hd), dtype=jnp.float32)
               for kk in ks)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_flash_attention_bf16(dtype):
    ks = jax.random.split(KEY, 3)
    q, k, v = (jax.random.normal(kk, (2, 128, 2, 32), dtype=dtype)
               for kk in ks)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        rtol=3e-2, atol=3e-2)


def test_jnp_flash_vjp_matches_naive_autodiff():
    """The custom VJP of the model-side jnp flash attention must match
    autodiff through the naive implementation."""
    from repro.models.layers import chunked_attention, naive_attention
    ks = jax.random.split(KEY, 3)
    q, k, v = (jax.random.normal(kk, (2, 100, 3, 32)) for kk in ks)

    def f(q, k, v):
        return (chunked_attention(q, k, v, causal=True, window=0,
                                  q_block=32, kv_block=48) ** 2).sum()

    def g(q, k, v):
        return (naive_attention(q, k, v, causal=True, window=0) ** 2).sum()

    g1 = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_kmeans_inside_lloyd_converges():
    """Pallas assignment inside Lloyd's recovers 4 well-separated blobs
    (interpret selected by the ops platform dispatch, not hard-coded)."""
    from repro.core.clustering import kmeans
    rng = np.random.default_rng(0)
    centers = rng.normal(size=(4, 16)) * 10
    pts = np.concatenate([c + rng.normal(size=(50, 16)) for c in centers])
    labels, cent = kmeans(
        jnp.asarray(pts, jnp.float32), 4, jax.random.PRNGKey(0),
        assign_fn=lambda x, c: ops.kmeans_assign(x, c, impl="pallas"))
    lab = np.asarray(labels).reshape(4, 50)
    for g in range(4):
        assert len(np.unique(lab[g])) == 1   # each blob in one cluster
    assert len(np.unique(lab[:, 0])) == 4    # blobs in distinct clusters
