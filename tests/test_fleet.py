"""Device-resident fleet pipeline (repro.sim.fleet + the ``device``
runtime): plan-cache/oracle bit-equality, capacity-class invariants, the
compile-once guarantee (zero retraces across shifting cohorts), and the
server's async round loop (fused eval, eval cadence, deferred metric
fetches)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import FLConfig
from repro.core.adapters import cnn_adapter
from repro.core.server import FederatedServer
from repro.data.partition import partition_clients
from repro.data.synthetic import make_image_dataset
from repro.sim.cohort import HostPlanCache, oracle_batch_plan
from repro.sim.fleet import FleetStore
from repro.sim.runtime import make_runtime

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


@pytest.fixture(scope="module")
def clients(data):
    train, _ = data
    return partition_clients(train.y, _cfg(), seed=3)


# ----------------------------------------------------------------------
# host plan cache: permutation-only rebuild == the oracle's full plan
# ----------------------------------------------------------------------

def test_plan_cache_matches_oracle(data, clients):
    train, _ = data
    cfg = _cfg()
    cache = HostPlanCache(train.x, train.y, clients, cfg.local_epochs)
    for i in range(N_CLIENTS):
        for hist in (0, 1, 5):
            n = clients[i].size
            bs = min(32, n)
            rng = np.random.default_rng(hist * 977 + i)
            ref = oracle_batch_plan(n, bs, cfg.local_epochs, rng)
            got = cache.plan(i, hist)
            assert (got == ref).all()
            # local gather == global gather through the shard
            xl, yl = cache.local_data(i)
            shard = np.asarray(clients[i].train_idx)
            assert (xl[got] == train.x[shard[ref]]).all()
            assert (yl[got] == train.y[shard[ref]]).all()


# ----------------------------------------------------------------------
# capacity classes: static cover of the fleet
# ----------------------------------------------------------------------

def test_capacity_classes_cover_fleet(data, clients):
    train, _ = data
    cfg = _cfg()
    store = FleetStore(train.x, train.y, clients, cfg)
    seen = set()
    for cls_id, c in enumerate(store.classes):
        for r, gid in enumerate(c.members):
            assert store.class_of[gid] == cls_id
            assert store.row_of[gid] == r
            assert gid not in seen
            seen.add(int(gid))
            # the client's whole plan fits the class capacities: a shard
            # smaller than the class batch runs one step of n samples
            n = clients[gid].size
            assert min(32, n) <= c.bs == min(32, c.n_cap)
            assert n <= c.n_cap
            total = (n // min(32, n)) * cfg.local_epochs
            assert total <= c.step_cap
            # the resident flat rows, reshaped to the image shape, are
            # exactly the client's local shard; the padding is zero
            xl, yl = store.cache.local_data(int(gid))
            size = int(np.prod(c.feat))
            row = np.asarray(c.x[r])
            assert (row[:n, :size].reshape((n,) + c.feat) == xl).all()
            assert not row[n:].any() and not row[:, size:].any()
            assert (np.asarray(c.y[r, :n]) == yl).all()
        # flat sample rows, padded to whole (8, 128) tiles
        assert c.feat == train.x.shape[1:]
        assert c.x.shape == (len(c.members), -(-c.n_cap // 8) * 8, 896)
        assert c.y.shape == c.x.shape[:2]
        assert c.tiers == sorted(set(c.tiers))
        assert c.step_cap % 4 == 0
    assert seen == {i for i in range(N_CLIENTS) if clients[i].size > 0}


def test_assemble_weights_and_masks(data, clients):
    train, _ = data
    cfg = _cfg()
    store = FleetStore(train.x, train.y, clients, cfg)
    sel = np.arange(N_CLIENTS)
    hist = np.arange(N_CLIENTS) % 3
    batches = store.assemble(sel, hist)
    sizes = np.array([c.size for c in clients], np.float64)
    pk = sizes / sizes.sum()
    seen = {}
    total_w = 0.0
    for b in batches:
        c = store.classes[b.cls_id]
        assert len(b.rows) in c.tiers
        for r, gid in enumerate(b.client_idx):
            if gid < 0:                       # padding row: fully masked
                assert b.step_mask[r].sum() == 0
                assert b.weights[r] == 0
                continue
            n = clients[gid].size
            steps = (n // min(32, n)) * cfg.local_epochs
            assert b.rows[r] == store.row_of[gid]
            assert b.step_mask[r].sum() == steps
            assert b.weights[r] == pytest.approx(pk[gid])
            seen[int(gid)] = seen.get(int(gid), 0) + 1
        total_w += float(b.weights.sum())
    assert sorted(seen) == list(range(N_CLIENTS))   # each winner once
    assert total_w == pytest.approx(1.0)
    assert store.assemble(np.array([], np.int64), hist) == []


# ----------------------------------------------------------------------
# compile-once policy: zero retraces across shifting cohorts
# ----------------------------------------------------------------------

def test_device_runtime_zero_retrace_across_shifting_cohorts(data,
                                                             clients):
    train, _ = data
    cfg = _cfg(runtime="device")
    adapter = cnn_adapter("mnist")
    params = adapter.init(jax.random.PRNGKey(0))
    rt = make_runtime(cfg, adapter, train.x, train.y, clients)
    st0 = obs.jax_stats.snapshot()
    rt.warmup(params)
    warm = obs.jax_stats.delta(st0)
    assert warm["traces/cohort_engine"] == sum(len(c.tiers)
                                               for c in rt.store.classes)
    hist = np.zeros(N_CLIENTS, np.int64)
    st1 = obs.jax_stats.snapshot()
    # 3+ rounds with shifting cohort sizes AND compositions, including
    # one bigger than any tier (chunked invocations reuse the shapes)
    for sel in (np.arange(N_CLIENTS), np.array([0, 3]),
                np.array([1, 4, 6, 7, 9]), np.array([2])):
        p = rt.train_cohort(params, sel, hist)
        assert p is not None
        hist[sel] += 1
    after = obs.jax_stats.delta(st1)
    assert "traces/cohort_engine" not in after, (warm, after)
    assert "shape_misses" not in after, (warm, after)
    assert after["shape_hits"] > 0


def test_stage3_counts_equal_the_class_batches(data, clients,
                                               monkeypatch):
    """The stage-3 work counts (fleet.class_work) against the ClassBatch
    arrays, and the device runtime adding them to obs.jax_stats only
    while obs records."""
    import repro.sim.runtime as RT
    from repro.sim.fleet import class_work
    train, _ = data
    cfg = _cfg(runtime="device", cohort_vmap_width=2)
    adapter = cnn_adapter("mnist")
    params = adapter.init(jax.random.PRNGKey(0))
    rt = make_runtime(cfg, adapter, train.x, train.y, clients)
    sel, hist = np.arange(N_CLIENTS), np.zeros(N_CLIENTS, np.int64)
    batches = rt.store.assemble(sel, hist)
    want = {"calls": len(batches), "serial_steps": 0, "step_slots": 0,
            "steps_real": 0, "sample_slots": 0, "samples_real": 0}
    for b in batches:
        tier, cap = b.step_mask.shape
        c = rt.store.classes[b.cls_id]
        assert cap == c.step_cap
        width = 2 if tier % 2 == 0 else 1     # vmap width dividing tier
        want["serial_steps"] += cap * tier // width
        want["step_slots"] += tier * cap
        want["steps_real"] += int((b.step_mask > 0).sum())
        for gid, steps in zip(b.client_idx, (b.step_mask > 0).sum(1)):
            if gid >= 0:
                want["sample_slots"] += int(steps) * c.bs
                want["samples_real"] += int(steps) * min(
                    32, clients[gid].size)
    got = class_work(batches, lambda r: rt.engine.client_chunks(r, True))
    assert got == want
    assert want["steps_real"] < want["step_slots"]
    assert want["samples_real"] < want["sample_slots"]

    calls = []
    monkeypatch.setattr(RT, "class_work",
                        lambda *a: calls.append(a) or class_work(*a))
    st = obs.jax_stats.snapshot()
    rt.train_cohort(params, sel, hist)           # obs off: no counting
    assert calls == []
    obs.configure(memory=True)
    try:
        rt.train_cohort(params, sel, hist)
    finally:
        obs.OBS.reset()
    moved = {k: v for k, v in obs.jax_stats.delta(st).items()
             if k.startswith("stage3/")}
    assert moved == {"stage3/assemblies": 1,
                     **{f"stage3/{k}": v for k, v in want.items()}}


def test_device_trace_program_names(data):
    """bench/metrics/*_device_ms_per_round find the round step, the class
    programs and the eval by these module names on the device trace's
    'XLA Modules' line."""
    from repro.core import rounds as RND
    srv = _server(_cfg(runtime="device"), data)
    rt = srv.runtime
    b = rt.store.warmup_batches()[0]

    def name(lowered):
        return lowered.compiler_ir().operation.attributes["sym_name"].value

    assert name(RND._round_step_jit.lower(
        srv.state, srv.key, None, None, srv.cfg,
        "segmented")) == "jit__round_step_jit"
    c = rt.store.classes[b.cls_id]
    assert name(rt.engine._train_gather.lower(
        srv.params, *rt._put_batch(b, c), feat=c.feat)) == "jit_train"
    assert name(srv._eval_step.lower(srv.params,
                                     srv._test_dev)) == "jit__eval"


# ----------------------------------------------------------------------
# async server loop: fused eval, cadence, deferred fetches
# ----------------------------------------------------------------------

def _server(cfg, data):
    train, test = data
    clients = partition_clients(train.y, cfg, seed=3)
    return FederatedServer(cfg, cnn_adapter("mnist"), train.x, train.y,
                           clients, {"x": test.x[:64], "y": test.y[:64]})


def test_fused_eval_matches_separate_calls(data):
    srv = _server(_cfg(), data)
    acc, loss = jax.device_get(srv._eval_step(srv.params, srv._test_dev))
    assert float(acc) == float(srv.adapter.accuracy(srv.params,
                                                    srv.test_batch))
    assert float(loss) == float(srv.adapter.loss(srv.params,
                                                 srv.test_batch))


@pytest.mark.parametrize("runtime", ("sequential", "device"))
def test_eval_every_cadence_and_equivalence(data, runtime):
    """eval_every>1 must change ONLY which rounds carry eval scalars:
    selection/energy logs and final params stay identical, skipped
    rounds log NaN, the final round always evaluates."""
    rounds = 5
    every = _server(_cfg(runtime=runtime, rounds=rounds), data)
    sparse = _server(_cfg(runtime=runtime, rounds=rounds, eval_every=3),
                     data)
    logs_e = every.run()
    logs_s = sparse.run()
    assert [not math.isnan(l.test_acc) for l in logs_s] == \
        [True, False, False, True, True]
    for le, ls in zip(logs_e, logs_s):
        assert (le.selected == ls.selected).all()
        assert le.energy_std == ls.energy_std
        assert le.mean_bid == ls.mean_bid
        assert le.client_reward_sum == ls.client_reward_sum
        if not math.isnan(ls.test_acc):
            assert le.test_acc == ls.test_acc
            assert le.test_loss == ls.test_loss
    diff = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))),
        every.params, sparse.params)))
    assert diff == 0.0
    assert every.total_client_reward == pytest.approx(
        sparse.total_client_reward)


def test_run_round_flushes_immediately(data):
    srv = _server(_cfg(runtime="device"), data)
    log = srv.run_round(0)
    assert srv._pending == []
    assert log.round == 0 and np.isfinite(log.test_acc)
    assert len(srv.logs) == 1


# ----------------------------------------------------------------------
# shards smaller than a batch: one masked-batch class per step band
# ----------------------------------------------------------------------

MERGED_CLIENTS = 40
MERGED_POOL = 2000      # varpi 50: shards of 8..80 training samples


def _merged_cfg(**kw):
    return _cfg(num_clients=MERGED_CLIENTS, num_clusters=4,
                select_ratio=0.25, local_epochs=1, **kw)


@pytest.fixture(scope="module")
def merged():
    train, _ = make_image_dataset("mnist", n_train=MERGED_POOL, n_test=64,
                                  seed=5)
    clients = partition_clients(train.y, _merged_cfg(), seed=5)
    sizes = np.array([c.size for c in clients])
    assert sizes.min() < 32 < sizes.max()
    return train, clients


class _Shard:
    def __init__(self, n):
        self.train_idx = np.arange(n)

    @property
    def size(self):
        return len(self.train_idx)


def _size_table(pool: int, n: int) -> np.ndarray:
    """Training shard sizes of the benchmark's partition
    (bench/gen/partition.local_sizes over [varpi/6, 2 varpi], then the
    80 % train split)."""
    varpi = pool // n
    lo = max(int(varpi / 6), 10)
    hi = max(int(varpi * 2.0), lo + 1)
    k = np.arange(n, dtype=np.float64)
    sizes = lo + np.floor((hi - lo + 1) * (k + 0.5) / n).astype(np.int64)
    return (0.8 * sizes).astype(np.int64)


def _bands(sizes, epochs=1):
    steps = sizes // np.minimum(32, sizes) * epochs
    return {1 << int(max(s, 1) - 1).bit_length() for s in steps}


@pytest.mark.parametrize("name,clients,pool,classes,programs", [
    ("cpu", 40, 2000, None, None),
    ("paper100", 100, 60000, 5, 21),
    ("emnist3383", 3383, 341873, 4, 37),
])
def test_shards_below_the_batch_share_the_full_batch_class(
        name, clients, pool, classes, programs):
    """One class per power-of-two step band, whatever the shard sizes in
    it: a fleet's shards smaller than a batch pad into its band's
    full-batch class, and every row states its real samples a step."""
    sizes = _size_table(pool, clients)
    x = np.zeros((int(sizes.max()), 1), np.float32)
    y = np.zeros((int(sizes.max()),), np.int32)
    cfg = FLConfig(num_clients=clients, num_clusters=10, select_ratio=0.1,
                   local_epochs=1)
    store = FleetStore(x, y, [_Shard(int(n)) for n in sizes], cfg)
    assert len(store.classes) == len(_bands(sizes))
    if classes is not None:
        assert len(store.classes) == classes
        assert sum(len(c.tiers) for c in store.classes) == programs
    for c in store.classes:
        n = sizes[c.members]
        assert c.bs == min(32, int(n.max()))
    padded = any((np.minimum(32, sizes[c.members]) < c.bs).any()
                 for c in store.classes)
    assert padded == bool((sizes < 32).any())
    # a padded member's plan rows carry its n real samples first
    batches = store.assemble(np.arange(clients), np.zeros(clients, int))
    for b in batches:
        for r, gid in enumerate(b.client_idx):
            if gid < 0:                     # padding row: a full batch
                assert b.batch_n[r] == store.classes[b.cls_id].bs
                continue
            n = min(32, int(sizes[gid]))
            assert b.batch_n[r] == n
            plan = b.plans[r, b.step_mask[r] > 0]
            assert len(set(plan[0, :n])) == n
            assert plan[:, :n].max() < sizes[gid]
            assert (plan[:, n:] == 0).all()


@pytest.mark.parametrize("multiple", [1, 4])
def test_assembled_rows_and_plans_index_inside_the_store(merged, multiple):
    """The class programs take winner rows and plan samples with
    ``mode="clip"``, which equals the default fill only for in-range
    indices: every row ``assemble`` (or the warm-up) writes lies in
    ``[0, P)`` and every plan index in ``[0, n)`` of its member's ``n``
    samples (padding rows and slots index 0), here with shards below a
    batch, winners chunked at each class's top tier, and tiers rounded
    to a 4-device mesh's multiple."""
    train, clients = merged
    store = FleetStore(train.x, train.y, clients, _merged_cfg(),
                       client_multiple=multiple)
    sizes = np.array([c.size for c in clients])
    hist = np.arange(MERGED_CLIENTS) % 4
    batches = store.assemble(np.arange(MERGED_CLIENTS), hist)
    assert any(len(b.rows) == store.classes[b.cls_id].client_cap
               for b in batches)
    assert (multiple > 1) == any((b.client_idx < 0).any() for b in batches)
    batches += store.warmup_batches()
    assert any(n < store.classes[store.class_of[g]].bs
               for g, n in enumerate(sizes))

    def take(c, b, mode):
        """The class program's takes: winner rows, then plan samples."""
        xs = jnp.take(c.x, b.rows, axis=0, mode=mode)
        return np.asarray(jax.vmap(
            lambda x, p: jnp.take(x, p, axis=0, mode=mode))(xs, b.plans))

    for b in batches:
        c = store.classes[b.cls_id]
        assert ((0 <= b.rows) & (b.rows < len(c.members))).all()
        assert b.plans.min() >= 0
        for r, gid in enumerate(b.client_idx):
            if gid < 0:
                assert b.rows[r] == 0 and not b.plans[r].any()
                continue
            assert c.members[b.rows[r]] == gid
            assert b.plans[r].max() < sizes[gid]
        # so the clip takes read what the fill takes would
        assert (take(c, b, "clip") == take(c, b, "fill")).all()


def test_device_runtime_matches_sequential_on_padded_batches(merged):
    """Three rounds of the masked-batch classes against the oracle's
    per-client loop, each round from the last one's params."""
    train, clients = merged
    adapter = cnn_adapter("mnist")
    params = adapter.init(jax.random.PRNGKey(1))
    cfg = _merged_cfg()
    seq = make_runtime(cfg.replace(runtime="sequential"), adapter, train.x,
                       train.y, clients)
    dev = make_runtime(cfg.replace(runtime="device"), adapter, train.x,
                       train.y, clients)
    assert any(min(32, clients[g].size) < c.bs
               for c in dev.store.classes for g in c.members)
    hist = np.zeros(MERGED_CLIENTS, np.int64)
    p_seq = p_dev = params
    small = [i for i, c in enumerate(clients) if c.size < 32]
    for sel in (np.arange(0, MERGED_CLIENTS, 3), np.array(small),
                np.arange(MERGED_CLIENTS)):
        p_seq = seq.train_cohort(p_seq, sel, hist)
        p_dev = dev.train_cohort(p_dev, sel, hist)
        diff = max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), p_seq, p_dev)))
        assert diff < 1e-4
        hist[sel] += 1


def test_padded_batch_classes_do_not_retrace_after_warmup(merged):
    train, clients = merged
    adapter = cnn_adapter("mnist")
    params = adapter.init(jax.random.PRNGKey(0))
    rt = make_runtime(_merged_cfg(runtime="device"), adapter, train.x,
                      train.y, clients)
    programs = sum(len(c.tiers) for c in rt.store.classes)
    st0 = obs.jax_stats.snapshot()
    rt.warmup(params)
    warm = obs.jax_stats.delta(st0)
    assert warm["traces/cohort_engine"] == programs
    assert warm["fleet/programs"] == programs
    hist = np.zeros(MERGED_CLIENTS, np.int64)
    st1 = obs.jax_stats.snapshot()
    rng = np.random.default_rng(0)
    for k in (1, 3, 7, 10, 2):
        sel = np.sort(rng.choice(MERGED_CLIENTS, k, replace=False))
        assert rt.train_cohort(params, sel, hist) is not None
        hist[sel] += 1
    after = obs.jax_stats.delta(st1)
    assert "traces/cohort_engine" not in after, (warm, after)
    assert "shape_misses" not in after, (warm, after)


def test_stage3_sample_counts_add_up(merged):
    """``stage3/sample_slots`` counts each real step's batch slots and
    ``stage3/samples_real`` the samples the oracle's batch holds."""
    train, clients = merged
    adapter = cnn_adapter("mnist")
    params = adapter.init(jax.random.PRNGKey(0))
    rt = make_runtime(_merged_cfg(runtime="device"), adapter, train.x,
                      train.y, clients)
    sel = np.arange(MERGED_CLIENTS)
    hist = np.zeros(MERGED_CLIENTS, np.int64)
    sizes = np.array([c.size for c in clients])
    bs = np.minimum(32, sizes)
    steps = sizes // bs
    slots = sum(int(steps[i]) * rt.store.classes[rt.store.class_of[i]].bs
                for i in sel)
    st = obs.jax_stats.snapshot()
    obs.configure(memory=True)
    try:
        rt.train_cohort(params, sel, hist)
    finally:
        obs.OBS.reset()
    moved = obs.jax_stats.delta(st)
    assert moved["stage3/samples_real"] == int((steps * bs).sum())
    assert moved["stage3/sample_slots"] == slots
    assert moved["stage3/samples_real"] < moved["stage3/sample_slots"]
