"""Cohort execution engine (repro.sim): packing invariants and
sequential-vs-{vectorized,sharded} equivalence across schemes and uneven
shards.  In this process the sharded runtime runs on the 1-device debug
mesh (same shard_map program, data axis size 1); the forced-8-device CPU
mesh is exercised by the subprocess test at the bottom (XLA_FLAGS must be
set before first jax init — see launch/mesh.py)."""
import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core.adapters import cnn_adapter
from repro.core.server import FederatedServer
from repro.data.partition import ClientData, partition_clients
from repro.data.synthetic import make_image_dataset
from repro.sim.cohort import (oracle_batch_plan, pack_cohort,
                              sequential_batch_plan)
from repro.sim.runtime import make_runtime

ENGINE_RUNTIMES = ("vectorized", "sharded", "device")

# small pool + strong imbalance: some clients hold fewer than 32 train
# samples, so packing produces several batch-size buckets and clients
# with unequal step counts (exercising the padding masks)
N_CLIENTS = 10
POOL = 700


def _cfg(**kw):
    base = dict(num_clients=N_CLIENTS, num_clusters=3, select_ratio=0.4,
                rounds=2, local_epochs=2, sample_window=10,
                cluster_resamples=2, init_energy_mode="normal", seed=3)
    base.update(kw)
    return FLConfig(**base)


@pytest.fixture(scope="module")
def data():
    train, test = make_image_dataset("mnist", n_train=POOL, n_test=120,
                                     seed=3)
    return train, test


def _server(cfg, data):
    train, test = data
    clients = partition_clients(train.y, cfg, seed=3)
    return FederatedServer(cfg, cnn_adapter("mnist"), train.x, train.y,
                           clients, {"x": test.x[:64], "y": test.y[:64]})


# ----------------------------------------------------------------------
# packing invariants
# ----------------------------------------------------------------------

def test_oracle_batch_plan_matches_loop():
    rng = np.random.default_rng(7)
    plan = oracle_batch_plan(100, 32, 2, rng)
    rng2 = np.random.default_rng(7)
    rows = []
    for _ in range(2):
        order = rng2.permutation(100)
        for i in range(0, 100 - 32 + 1, 32):
            rows.append(order[i:i + 32])
    assert (plan == np.stack(rows)).all()
    assert plan.shape == (6, 32)          # 3 full batches per epoch


def test_sequential_plan_drops_remainder():
    plan = sequential_batch_plan(70, 32)
    assert plan.shape == (2, 32)
    assert (plan == np.arange(64).reshape(2, 32)).all()


def test_pack_cohort_masks_and_weights(data):
    cfg = _cfg()
    train, _ = data
    clients = partition_clients(train.y, cfg, seed=3)
    sel = np.arange(N_CLIENTS)
    hist = np.zeros(N_CLIENTS, np.int64)
    buckets = pack_cohort(train.x, train.y, clients, sel, hist, cfg)
    sizes = np.array([c.size for c in clients], np.float64)
    pk = sizes / sizes.sum()
    seen = {}
    for b in buckets:
        assert b.step_mask.shape == b.xb.shape[:2] == b.yb.shape[:2]
        assert b.xb.shape[2] == b.batch_size
        for row, cid in enumerate(b.client_idx):
            if cid < 0:                        # padding row: fully masked
                assert b.step_mask[row].sum() == 0
                assert b.weights[row] == 0
            else:
                n = clients[cid].size
                bs = min(32, n)
                steps = (n - bs) // bs + 1
                assert b.batch_size == bs
                assert b.step_mask[row].sum() == steps * cfg.local_epochs
                assert b.weights[row] == pytest.approx(pk[cid])
                seen[int(cid)] = seen.get(int(cid), 0) + 1
    assert sorted(seen) == list(range(N_CLIENTS))   # each client once
    total_w = sum(float(b.weights.sum()) for b in buckets)
    assert total_w == pytest.approx(1.0)
    assert len(buckets) > 1       # uneven shards -> several buckets


# ----------------------------------------------------------------------
# CNN hot-path oracles (XLA's conv against im2col, reshape maxpool
# against reduce_window — the engine's vmap path runs both, DESIGN.md)
# ----------------------------------------------------------------------

def _im2col_conv2d(x, w, b, padding="VALID"):
    """Oracle: the conv as patches @ kernel, the kh*kw shifted views of
    ``x`` concatenated on the channel axis, in one float32 GEMM."""
    kh, kw, cin, cout = w.shape
    if padding == "SAME":
        ph, pw = (kh - 1) // 2, (kw - 1) // 2
        x = jnp.pad(x, ((0, 0), (ph, kh - 1 - ph), (pw, kw - 1 - pw),
                        (0, 0)))
    oh, ow = x.shape[1] - kh + 1, x.shape[2] - kw + 1
    patches = jnp.concatenate([x[:, i:i + oh, j:j + ow, :]
                               for i in range(kh) for j in range(kw)], -1)
    return jnp.dot(patches, w.reshape(kh * kw * cin, cout),
                   precision=jax.lax.Precision.HIGHEST) + b


def _conv_program(conv, transform, padding):
    """``conv`` as the engine runs it: alone, vmapped over per-client
    weights, or differentiated in all three operands."""
    f = functools.partial(conv, padding=padding)
    if transform == "vmap":
        return jax.jit(jax.vmap(f))
    if transform == "grad":
        return jax.jit(jax.grad(lambda x, w, b: jnp.sum(f(x, w, b) ** 2),
                                argnums=(0, 1, 2)))
    return jax.jit(f)


@pytest.mark.parametrize("padding,cin,cout,transform", [
    pytest.param(*case, transform, id="-".join(map(str, case)) + (
        "" if transform == "plain" else f"-{transform}"))
    for transform in ("plain", "vmap", "grad")
    for case in (("VALID", 1, 10), ("VALID", 3, 6), ("SAME", 1, 16),
                 ("SAME", 16, 32))
])
def test_conv2d_im2col_matches_lax(padding, cin, cout, transform):
    """``conv2d`` (XLA's convolution) equals the im2col oracle alone,
    under the engine's per-client vmap, and in its gradients."""
    from repro.models.cnn import conv2d
    key = jax.random.PRNGKey(0)
    lead = (3,) if transform == "vmap" else ()
    x = jax.random.normal(key, lead + (4, 14, 14, cin))
    w = jax.random.normal(jax.random.fold_in(key, 1),
                          lead + (5, 5, cin, cout))
    b = jax.random.normal(jax.random.fold_in(key, 2), lead + (cout,))
    got, ref = (jax.tree.leaves(_conv_program(f, transform, padding)(x, w, b))
                for f in (conv2d, _im2col_conv2d))
    for g, r in zip(got, ref, strict=True):
        assert g.shape == r.shape
        # a gradient sums ~10^3 products of magnitude ~10: relative
        scale = float(jnp.max(jnp.abs(r))) if transform == "grad" else 1.0
        assert float(jnp.max(jnp.abs(g - r))) < 1e-4 * scale


@pytest.mark.parametrize("h,w", [(24, 24), (7, 7), (14, 10)])
def test_maxpool2_matches_reduce_window(h, w):
    from jax import lax
    from repro.models.cnn import maxpool2
    x = jax.random.normal(jax.random.PRNGKey(1), (3, h, w, 5))
    ref = lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                            (1, 2, 2, 1), "VALID")
    assert (maxpool2(x) == ref).all()


# ----------------------------------------------------------------------
# engine vs oracle equivalence
# ----------------------------------------------------------------------

def _max_param_diff(p1, p2) -> float:
    return max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), p1, p2)))


@pytest.mark.parametrize("runtime", ENGINE_RUNTIMES)
def test_train_cohort_matches_oracle(data, runtime):
    """One cohort, every client, nonzero histories: aggregated params of
    the engine backends agree with the oracle up to float reassociation
    (sharded runs on the 1-device debug mesh here)."""
    cfg = _cfg()
    train, _ = data
    clients = partition_clients(train.y, cfg, seed=3)
    adapter = cnn_adapter("mnist")
    params = adapter.init(jax.random.PRNGKey(0))
    hist = np.arange(N_CLIENTS) % 3
    sel = np.arange(N_CLIENTS)
    seq = make_runtime(cfg.replace(runtime="sequential"), adapter,
                       train.x, train.y, clients)
    eng = make_runtime(cfg.replace(runtime=runtime), adapter,
                       train.x, train.y, clients)
    p_seq = seq.train_cohort(params, sel, hist)
    p_eng = eng.train_cohort(params, sel, hist)
    assert _max_param_diff(p_seq, p_eng) < 1e-4


@pytest.mark.parametrize("runtime", ENGINE_RUNTIMES)
def test_train_cohort_empty_is_noop(data, runtime):
    cfg = _cfg(runtime=runtime)
    train, _ = data
    clients = partition_clients(train.y, cfg, seed=3)
    adapter = cnn_adapter("mnist")
    params = adapter.init(jax.random.PRNGKey(0))
    rt = make_runtime(cfg, adapter, train.x, train.y, clients)
    assert rt.train_cohort(params, np.array([], np.int64),
                           np.zeros(N_CLIENTS)) is None


def _zero_size_client() -> ClientData:
    e = np.empty((0,), np.int64)
    return ClientData(train_idx=e, val_idx=e, test_idx=e, primary_label=0)


@pytest.mark.parametrize("runtime",
                         ("sequential",) + ENGINE_RUNTIMES)
def test_all_zero_size_cohort_skips_aggregation(data, runtime):
    """Winners with no local samples must not zero the global params: an
    all-zero cohort returns None (the old sequential path multiplied the
    params by an all-zero ``pk`` vector)."""
    cfg = _cfg(runtime=runtime)
    train, _ = data
    clients = [_zero_size_client() for _ in range(3)]
    adapter = cnn_adapter("mnist")
    params = adapter.init(jax.random.PRNGKey(0))
    rt = make_runtime(cfg, adapter, train.x, train.y, clients)
    assert rt.train_cohort(params, np.arange(3), np.zeros(3)) is None


@pytest.mark.parametrize("runtime", ENGINE_RUNTIMES)
def test_zero_size_winner_dropped_from_cohort(data, runtime):
    """A zero-size winner among real ones is dropped; the remaining
    cohort matches the oracle on the same reduced selection."""
    cfg = _cfg()
    train, _ = data
    clients = (list(partition_clients(train.y, cfg, seed=3))[:4]
               + [_zero_size_client()])
    adapter = cnn_adapter("mnist")
    params = adapter.init(jax.random.PRNGKey(0))
    hist = np.zeros(5, np.int64)
    seq = make_runtime(cfg.replace(runtime="sequential"), adapter,
                       train.x, train.y, clients)
    eng = make_runtime(cfg.replace(runtime=runtime), adapter,
                       train.x, train.y, clients)
    p_seq = seq.train_cohort(params, np.arange(5), hist)   # drops idx 4
    p_ref = seq.train_cohort(params, np.arange(4), hist)
    p_eng = eng.train_cohort(params, np.arange(5), hist)
    assert _max_param_diff(p_seq, p_ref) == 0.0
    assert _max_param_diff(p_seq, p_eng) < 1e-4


def test_weight_features_missing_client_raises(data):
    """A client id never placed in any bucket must fail loudly (the old
    path died inside jnp.stack with an opaque TypeError)."""
    cfg = _cfg(runtime="vectorized")
    train, _ = data
    clients = partition_clients(train.y, cfg, seed=3)
    adapter = cnn_adapter("mnist")
    params = adapter.init(jax.random.PRNGKey(0))
    rt = make_runtime(cfg, adapter, train.x, train.y, clients)
    from repro.sim.cohort import pack_feature_pass
    buckets = pack_feature_pass(train.x, train.y, clients,
                                chunk_width=cfg.cohort_vmap_width)
    with pytest.raises(ValueError, match="missing from the packed buckets"):
        # claim one more client than was packed -> id N has no row
        rt.engine.weight_features(params, buckets, len(clients) + 1)


@pytest.mark.parametrize("scheme,aggregator,runtime", [
    ("random", "fedavg", "vectorized"),
    ("gradient_cluster_auction", "fedavg", "vectorized"),
    ("gradient_cluster_auction", "fedprox", "vectorized"),
    ("gradient_cluster_auction", "fedavg", "sharded"),
    ("random", "fedavg", "device"),
    ("gradient_cluster_auction", "fedavg", "device"),
    ("gradient_cluster_auction", "fedprox", "device"),
])
def test_full_loop_equivalence(data, scheme, aggregator, runtime):
    """Engine runtimes produce identical RoundLog selection/energy fields
    and matching aggregated params over full rounds (clustering included
    for the auction scheme — the engine gradient-feature pass must
    reproduce the reference clustering exactly)."""
    logs, params = {}, {}
    for rt in ("sequential", runtime):
        srv = _server(_cfg(scheme=scheme, aggregator=aggregator,
                           runtime=rt), data)
        logs[rt] = srv.run()
        params[rt] = srv.params
    for l_seq, l_eng in zip(logs["sequential"], logs[runtime]):
        assert (l_seq.selected == l_eng.selected).all()
        assert l_seq.energy_std == l_eng.energy_std
        assert l_seq.mean_bid == l_eng.mean_bid
        assert l_seq.server_reward == l_eng.server_reward
    assert _max_param_diff(params["sequential"], params[runtime]) < 1e-4


# ----------------------------------------------------------------------
# forced multi-device mesh (subprocess: XLA_FLAGS must precede jax init)
# ----------------------------------------------------------------------

_FORCED_MESH_SCRIPT = r"""
import jax, numpy as np, jax.numpy as jnp
assert jax.local_device_count() == 8, jax.local_device_count()
from repro.configs.base import FLConfig
from repro.core.adapters import cnn_adapter
from repro.core.server import FederatedServer
from repro.data.partition import partition_clients
from repro.data.synthetic import make_image_dataset

cfg = FLConfig(num_clients=10, num_clusters=3, select_ratio=0.4, rounds=2,
               local_epochs=2, sample_window=10, cluster_resamples=2,
               init_energy_mode="normal", scheme="random", seed=3)
train, test = make_image_dataset("mnist", n_train=700, n_test=120, seed=3)
adapter = cnn_adapter("mnist")
logs, params = {}, {}
for rt in ("vectorized", "sharded", "device"):
    clients = partition_clients(train.y, cfg, seed=3)
    srv = FederatedServer(cfg.replace(runtime=rt), adapter, train.x,
                          train.y, clients,
                          {"x": test.x[:64], "y": test.y[:64]})
    if rt in ("sharded", "device"):
        assert srv.runtime.engine.data_axis_size == 8, \
            srv.runtime.engine.data_axis_size
    if rt == "device":
        # every tier must split evenly across the 8-way data axis
        for c in srv.runtime.store.classes:
            assert all(t % 8 == 0 for t in c.tiers), c.tiers
    logs[rt] = srv.run()
    params[rt] = srv.params
for other in ("sharded", "device"):
    for l_v, l_s in zip(logs["vectorized"], logs[other]):
        assert (l_v.selected == l_s.selected).all()
        assert l_v.energy_std == l_s.energy_std
        assert l_v.mean_bid == l_s.mean_bid
    diff = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))),
        params["vectorized"], params[other])))
    assert diff < 1e-4, (other, diff)
print("FORCED_MESH_OK", diff)
"""


def test_sharded_runtime_on_forced_8_device_mesh():
    """Full-loop vectorized-vs-sharded equivalence on a real 8-way client
    split: identical selection logs, params within the reassociation
    tolerance.  Runs in a subprocess because the device-count flag only
    takes effect before first jax init (launch/mesh.py caveat)."""
    env = dict(os.environ)
    # drop any ambient device-count forcing, then append ours (XLA takes
    # the LAST occurrence, so a developer's exported =4 would win a
    # naive prepend)
    kept = [f for f in env.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        kept + ["--xla_force_host_platform_device_count=8"])
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    r = subprocess.run([sys.executable, "-c", _FORCED_MESH_SCRIPT],
                       capture_output=True, text=True, env=env,
                       timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "FORCED_MESH_OK" in r.stdout
