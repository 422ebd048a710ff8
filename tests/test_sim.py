"""Cohort execution engine (repro.sim): packing invariants and
sequential-vs-device equivalence across schemes and uneven shards.  In
this process the ``device-mesh1`` variant runs the device runtime on the
1-device debug mesh (the shard_map class program, data axis size 1); the
forced-8-device CPU mesh is exercised by the subprocess test at the
bottom (XLA_FLAGS must be set before first jax init — see
launch/mesh.py)."""
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
from repro.launch.mesh import make_cohort_mesh
from repro.sim.cohort import (oracle_batch_plan, pack_feature_pass,
                              sequential_batch_plan)
from repro.sim.runtime import DeviceRuntime, make_runtime

# the device runtime as tested against the oracle: plain; with local SGD
# momentum (the class program's optimizer state, which momentum 0 leaves
# empty); and on a 1-device cohort mesh (the shard_map class program,
# in-process)
ENGINE_VARIANTS = ("device", "device-momentum", "device-mesh1")

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


def _variant_cfg(variant, cfg):
    """``cfg`` for ``variant``'s comparison, the oracle's run included."""
    if variant == "device-momentum":
        return cfg.replace(local_momentum=0.9)
    return cfg


def _runtime(variant, cfg, adapter, x, y, clients):
    if variant == "sequential":
        return make_runtime(cfg.replace(runtime="sequential"), adapter, x,
                            y, clients)
    mesh = make_cohort_mesh(1) if variant == "device-mesh1" else None
    return DeviceRuntime(cfg.replace(runtime="device"), adapter, x, y,
                         clients, mesh=mesh)


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


def test_pack_feature_pass_masks_and_rows(data):
    """The weight feature pass's packing: one in-order epoch per client,
    every client exactly once; padding rows are fully masked."""
    cfg = _cfg()
    train, _ = data
    clients = partition_clients(train.y, cfg, seed=3)
    buckets = pack_feature_pass(train.x, train.y, clients,
                                chunk_width=cfg.cohort_vmap_width)
    seen = {}
    for b in buckets:
        assert b.step_mask.shape == b.xb.shape[:2] == b.yb.shape[:2]
        assert b.xb.shape[2] == b.batch_size
        assert b.num_steps % 4 == 0       # step axis padded to whole 4s
        for row, cid in enumerate(b.client_idx):
            if cid < 0:                        # padding row: fully masked
                assert b.step_mask[row].sum() == 0
            else:
                shard = np.asarray(clients[cid].train_idx)
                n = shard.size
                bs = min(32, n)
                steps = n // bs
                assert b.batch_size == bs
                assert b.step_mask[row].sum() == steps
                # natural order, remainder dropped
                ref = train.x[shard[:steps * bs]].reshape(
                    (steps, bs) + train.x.shape[1:])
                assert (b.xb[row, :steps] == ref).all()
                assert (b.yb[row, :steps]
                        == train.y[shard[:steps * bs]].reshape(steps, bs)
                        ).all()
                seen[int(cid)] = seen.get(int(cid), 0) + 1
    assert seen == {c: 1 for c in range(N_CLIENTS)}   # each client once
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


@pytest.mark.parametrize("runtime", ENGINE_VARIANTS)
def test_train_cohort_matches_oracle(data, runtime):
    """One cohort, every client, nonzero histories: aggregated params of
    the device runtime agree with the oracle up to float reassociation."""
    cfg = _variant_cfg(runtime, _cfg())
    train, _ = data
    clients = partition_clients(train.y, cfg, seed=3)
    adapter = cnn_adapter("mnist")
    params = adapter.init(jax.random.PRNGKey(0))
    hist = np.arange(N_CLIENTS) % 3
    sel = np.arange(N_CLIENTS)
    seq = _runtime("sequential", cfg, adapter, train.x, train.y, clients)
    eng = _runtime(runtime, cfg, adapter, train.x, train.y, clients)
    p_seq = seq.train_cohort(params, sel, hist)
    p_eng = eng.train_cohort(params, sel, hist)
    assert _max_param_diff(p_seq, p_eng) < 1e-4


@pytest.mark.parametrize("runtime", ENGINE_VARIANTS)
def test_train_cohort_empty_is_noop(data, runtime):
    cfg = _variant_cfg(runtime, _cfg())
    train, _ = data
    clients = partition_clients(train.y, cfg, seed=3)
    adapter = cnn_adapter("mnist")
    params = adapter.init(jax.random.PRNGKey(0))
    rt = _runtime(runtime, cfg, adapter, train.x, train.y, clients)
    assert rt.train_cohort(params, np.array([], np.int64),
                           np.zeros(N_CLIENTS)) is None


def _zero_size_client() -> ClientData:
    e = np.empty((0,), np.int64)
    return ClientData(train_idx=e, val_idx=e, test_idx=e, primary_label=0)


@pytest.mark.parametrize("runtime",
                         ("sequential",) + ENGINE_VARIANTS)
def test_all_zero_size_cohort_skips_aggregation(data, runtime):
    """Winners with no local samples must not zero the global params: an
    all-zero cohort returns None (the old sequential path multiplied the
    params by an all-zero ``pk`` vector)."""
    cfg = _variant_cfg(runtime, _cfg())
    train, _ = data
    clients = [_zero_size_client() for _ in range(3)]
    adapter = cnn_adapter("mnist")
    params = adapter.init(jax.random.PRNGKey(0))
    rt = _runtime(runtime, cfg, adapter, train.x, train.y, clients)
    assert rt.train_cohort(params, np.arange(3), np.zeros(3)) is None


@pytest.mark.parametrize("runtime", ENGINE_VARIANTS)
def test_zero_size_winner_dropped_from_cohort(data, runtime):
    """A zero-size winner among real ones is dropped; the remaining
    cohort matches the oracle on the same reduced selection."""
    cfg = _variant_cfg(runtime, _cfg())
    train, _ = data
    clients = (list(partition_clients(train.y, cfg, seed=3))[:4]
               + [_zero_size_client()])
    adapter = cnn_adapter("mnist")
    params = adapter.init(jax.random.PRNGKey(0))
    hist = np.zeros(5, np.int64)
    seq = _runtime("sequential", cfg, adapter, train.x, train.y, clients)
    eng = _runtime(runtime, cfg, adapter, train.x, train.y, clients)
    p_seq = seq.train_cohort(params, np.arange(5), hist)   # drops idx 4
    p_ref = seq.train_cohort(params, np.arange(4), hist)
    p_eng = eng.train_cohort(params, np.arange(5), hist)
    assert _max_param_diff(p_seq, p_ref) == 0.0
    assert _max_param_diff(p_seq, p_eng) < 1e-4


def test_weight_features_missing_client_raises(data):
    """A client id never placed in any bucket must fail loudly (the old
    path died inside jnp.stack with an opaque TypeError)."""
    cfg = _cfg(runtime="device")
    train, _ = data
    clients = partition_clients(train.y, cfg, seed=3)
    adapter = cnn_adapter("mnist")
    params = adapter.init(jax.random.PRNGKey(0))
    rt = make_runtime(cfg, adapter, train.x, train.y, clients)
    buckets = pack_feature_pass(train.x, train.y, clients,
                                chunk_width=cfg.cohort_vmap_width,
                                cache=rt.plan_cache)
    with pytest.raises(ValueError, match="missing from the packed buckets"):
        # claim one more client than was packed -> id N has no row
        rt.engine.weight_features(params, buckets, len(clients) + 1)


@pytest.mark.parametrize("scheme,aggregator,runtime", [
    ("random", "fedavg", "device"),
    ("gradient_cluster_auction", "fedavg", "device"),
    ("gradient_cluster_auction", "fedprox", "device"),
    ("weights_cluster_random", "fedavg", "device"),
    ("gradient_cluster_random", "fedavg", "device"),
    ("random", "fedprox", "device"),
    ("gradient_cluster_auction", "fedavg", "device-momentum"),
])
def test_full_loop_equivalence(data, scheme, aggregator, runtime):
    """The device runtime produces identical RoundLog selection/energy
    fields and matching aggregated params over full rounds (clustering
    included for the cluster schemes — the device runtime's gradient and
    weight feature passes must reproduce the reference clustering
    exactly)."""
    logs, params = {}, {}
    for rt in ("sequential", runtime):
        cfg = _cfg(scheme=scheme, aggregator=aggregator,
                   runtime="sequential" if rt == "sequential" else "device")
        srv = _server(_variant_cfg(runtime, cfg), data)
        logs[rt] = srv.run()
        params[rt] = srv.params
    for l_seq, l_eng in zip(logs["sequential"], logs[runtime]):
        assert (l_seq.selected == l_eng.selected).all()
        assert l_seq.energy_std == l_eng.energy_std
        assert l_seq.mean_bid == l_eng.mean_bid
        assert l_seq.server_reward == l_eng.server_reward
    assert _max_param_diff(params["sequential"], params[runtime]) < 1e-4


@pytest.mark.parametrize("name", ("vectorized", "sharded"))
def test_unknown_runtime_names_the_two(data, name, capsys):
    """A runtime name outside ``RUNTIMES`` fails at construction and on
    the command line, naming ``sequential`` and ``device``."""
    from repro.launch import train as T
    from repro.sim.runtime import RUNTIMES
    assert RUNTIMES == ("sequential", "device")
    train, _ = data
    cfg = _cfg(runtime=name)
    clients = partition_clients(train.y, cfg, seed=3)
    with pytest.raises(ValueError, match="'sequential', 'device'"):
        make_runtime(cfg, cnn_adapter("mnist"), train.x, train.y, clients)
    with pytest.raises(SystemExit) as exc, \
            pytest.MonkeyPatch.context() as mp:
        mp.setattr(sys, "argv", ["train", "--runtime", name])
        T.main()
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert "sequential" in err and "device" in err and name in err


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
for rt in ("sequential", "device"):
    clients = partition_clients(train.y, cfg, seed=3)
    srv = FederatedServer(cfg.replace(runtime=rt), adapter, train.x,
                          train.y, clients,
                          {"x": test.x[:64], "y": test.y[:64]})
    if rt == "device":
        assert srv.runtime.engine.data_axis_size == 8, \
            srv.runtime.engine.data_axis_size
        # every tier must split evenly across the 8-way data axis
        for c in srv.runtime.store.classes:
            assert all(t % 8 == 0 for t in c.tiers), c.tiers
    logs[rt] = srv.run()
    params[rt] = srv.params
for l_q, l_d in zip(logs["sequential"], logs["device"]):
    assert (l_q.selected == l_d.selected).all()
    assert l_q.energy_std == l_d.energy_std
    assert l_q.mean_bid == l_d.mean_bid
diff = max(jax.tree.leaves(jax.tree.map(
    lambda a, b: float(jnp.max(jnp.abs(a - b))),
    params["sequential"], params["device"])))
assert diff < 1e-4, diff
print("FORCED_MESH_OK", diff)
"""


def test_device_runtime_on_forced_8_device_mesh():
    """Full-loop sequential-vs-device equivalence with the device
    runtime's class calls split 8 ways over the cohort mesh: identical
    selection logs, params within the reassociation tolerance.  Runs in
    a subprocess because the device-count flag only takes effect before
    first jax init (launch/mesh.py caveat)."""
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
