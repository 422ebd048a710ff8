"""Fleet dynamics (repro.sim.dynamics + the server's degraded
aggregation paths): fault-model semantics, the dedicated PRNG stream's
churn-0 bit-identity guarantee, cross-runtime outcome equivalence, the
buffered-aggregation oracle boundary, and the zero-survivor guard
(params pass through, ``round/empty`` logged, never a NaN)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import FLConfig
from repro.core import rounds as RND
from repro.core import selection as SEL
from repro.core.adapters import cnn_adapter
from repro.core.server import FederatedServer
from repro.data.partition import partition_clients
from repro.data.synthetic import make_image_dataset
from repro.obs.schema import load_jsonl, validate_events
from repro.sim import dynamics as DYN

RUNTIMES = ("sequential", "device")
N_CLIENTS = 10
POOL = 700


@pytest.fixture(autouse=True)
def _obs_reset():
    obs.OBS.reset()
    yield
    obs.OBS.reset()


def _cfg(**kw):
    base = dict(num_clients=N_CLIENTS, num_clusters=3, select_ratio=0.4,
                rounds=3, local_epochs=1, sample_window=10,
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


def _leaves(params):
    return [np.asarray(x) for x in jax.tree.leaves(params)]


def _assert_trees_equal(a, b):
    for x, y in zip(_leaves(a), _leaves(b)):
        np.testing.assert_array_equal(x, y)


# ----------------------------------------------------------------------
# fault model unit semantics
# ----------------------------------------------------------------------

def _fleet_arrays(n):
    win = jnp.zeros((n,), bool).at[jnp.arange(0, n, 2)].set(True)
    avail = jnp.ones((n,), bool)
    residual = jnp.linspace(10.0, 100.0, n).astype(jnp.float32)
    sizes = jnp.full((n,), 50, jnp.int32)
    return win, avail, residual, sizes


def test_fault_step_deterministic_and_well_formed():
    cfg = _cfg(churn=0.3, deadline=1.2)
    win, avail, residual, sizes = _fleet_arrays(cfg.num_clients)
    key = jax.random.PRNGKey(5)
    out1, lat1, av1 = DYN.fault_step(cfg, key, win, avail, residual, sizes)
    out2, lat2, av2 = DYN.fault_step(cfg, key, win, avail, residual, sizes)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    np.testing.assert_array_equal(np.asarray(lat1), np.asarray(lat2))
    np.testing.assert_array_equal(np.asarray(av1), np.asarray(av2))
    out, w = np.asarray(out1), np.asarray(win)
    assert (out[~w] == DYN.NOT_SELECTED).all()
    assert set(np.unique(out[w])) <= {DYN.COMPLETED, DYN.LATE, DYN.DROPPED}
    assert np.isfinite(np.asarray(lat1)).all() and (np.asarray(lat1) > 0).all()


def test_fault_step_no_faults_with_knobs_off():
    # churn 0 + no deadline: every winner completes, nobody churns out
    cfg = _cfg(churn=0.0, deadline=0.0)
    win, avail, residual, sizes = _fleet_arrays(cfg.num_clients)
    out, _, av = DYN.fault_step(cfg, jax.random.PRNGKey(1), win, avail,
                                residual, sizes)
    assert (np.asarray(out)[np.asarray(win)] == DYN.COMPLETED).all()
    assert np.asarray(av).all()


def test_fault_step_tiny_deadline_tags_every_winner_late():
    # 'none' profile: latency = compute + 0.05 > 1e-6 for everyone
    cfg = _cfg(churn=0.0, deadline=1e-6, straggler_profile="none")
    win, avail, residual, sizes = _fleet_arrays(cfg.num_clients)
    out, _, _ = DYN.fault_step(cfg, jax.random.PRNGKey(1), win, avail,
                               residual, sizes)
    assert (np.asarray(out)[np.asarray(win)] == DYN.LATE).all()


def test_staleness_counter_and_weight():
    stale = jnp.asarray([0, 2, 5], jnp.int32)
    out = jnp.asarray([DYN.COMPLETED, DYN.LATE, DYN.NOT_SELECTED],
                      jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(DYN.update_staleness(stale, out)), [0, 3, 6])
    cfg = _cfg(churn=0.1, staleness_alpha=0.5)
    assert DYN.staleness_weight(cfg, 0) == 1.0
    assert abs(DYN.staleness_weight(cfg, 3) - 0.5) < 1e-12


# ----------------------------------------------------------------------
# availability gating in selection
# ----------------------------------------------------------------------

@pytest.mark.parametrize("scheme", ["gradient_cluster_auction",
                                    "gradient_cluster_random"])
def test_select_round_avail_none_equals_all_ones(scheme):
    cfg = _cfg(scheme=scheme, num_clients=40, num_clusters=4)
    state = RND.synthetic_fleet(cfg, jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(7)
    w_none, _ = SEL.select_round(state, cfg, key)
    w_ones, _ = SEL.select_round(state, cfg, key,
                                 avail=jnp.ones((40,), bool))
    np.testing.assert_array_equal(np.asarray(w_none), np.asarray(w_ones))


def test_select_round_offline_clients_cannot_win():
    cfg = _cfg(scheme="gradient_cluster_auction", num_clients=40,
               num_clusters=4)
    state = RND.synthetic_fleet(cfg, jax.random.PRNGKey(0))
    avail = jnp.arange(40) % 2 == 0        # odd ids offline
    win, _ = SEL.select_round(state, cfg, jax.random.PRNGKey(7),
                              avail=avail)
    assert not bool((np.asarray(win) & ~np.asarray(avail)).any())


# ----------------------------------------------------------------------
# churn-0 regression: the dedicated dynamics key stream must leave
# dynamics-free runs bit-identical (selection logs AND params)
# ----------------------------------------------------------------------

def test_churn_zero_bit_identical_to_plain_config(data):
    cfg_plain = _cfg()
    # every dynamics knob changed EXCEPT churn/deadline (both 0): the
    # run must not see any of it — same code path, same key stream
    cfg_dyn0 = _cfg(churn=0.0, deadline=0.0,
                    straggler_profile="lognormal",
                    aggregation="buffered", buffer_goal=2,
                    staleness_alpha=1.0)
    assert not cfg_dyn0.dynamics_enabled
    sa, sb = _server(cfg_plain, data), _server(cfg_dyn0, data)
    la, lb = sa.run(), sb.run()
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(x.selected, y.selected)
        assert x.mean_bid == y.mean_bid
        assert x.energy_std == y.energy_std
    _assert_trees_equal(sa.params, sb.params)


# ----------------------------------------------------------------------
# cross-runtime equivalence under churn
# ----------------------------------------------------------------------

def test_outcome_masks_identical_across_runtimes(data):
    outs, sels, params = {}, {}, {}
    for rt in RUNTIMES:
        cfg = _cfg(runtime=rt, churn=0.25, deadline=1.2,
                   aggregation="buffered", buffer_goal=2)
        srv = _server(cfg, data)
        srv.run()
        outs[rt] = [o.tolist() for o in srv.outcome_log]
        sels[rt] = [l.selected.tolist() for l in srv.logs]
        params[rt] = srv.params
        for leaf in _leaves(srv.params):
            assert np.isfinite(leaf).all(), rt
    for rt in RUNTIMES[1:]:
        assert outs[rt] == outs["sequential"], rt
        assert sels[rt] == sels["sequential"], rt


def test_buffered_without_faults_matches_sync_oracle(data):
    # deadline huge + churn 0: dynamics path is ON but fault-free, so
    # every winner COMPLETES and the buffered server must walk the exact
    # synchronous-oracle trajectory (selections and params bit-equal —
    # the dedicated dyn key stream never touches the selection chain)
    cfg_sync = _cfg()
    cfg_buf = _cfg(churn=0.0, deadline=1e9, aggregation="buffered")
    assert cfg_buf.dynamics_enabled
    sa, sb = _server(cfg_sync, data), _server(cfg_buf, data)
    la, lb = sa.run(), sb.run()
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(x.selected, y.selected)
    assert all((o == DYN.COMPLETED).all() for o in sb.outcome_log)
    for x, y in zip(_leaves(sa.params), _leaves(sb.params)):
        np.testing.assert_allclose(x, y, rtol=2e-5, atol=2e-6)


# ----------------------------------------------------------------------
# zero-survivor guard + buffered fold events
# ----------------------------------------------------------------------

def test_zero_survivor_rounds_pass_params_through(data):
    mem = obs.configure(memory=True)
    cfg = _cfg(churn=1.0, rejoin_prob=0.0, replace_dropped=False)
    srv = _server(cfg, data)
    p0 = _leaves(srv.params)
    logs = srv.run()
    # every winner dropped every round: params untouched, never NaN
    for x, y in zip(p0, _leaves(srv.params)):
        np.testing.assert_array_equal(x, y)
    assert obs.OBS.counters.get("round/empty", 0) == cfg.rounds
    names = [e.get("name") for e in mem.events if e["kind"] == "dynamics"]
    assert names.count("round/empty") == cfg.rounds
    assert all(np.isfinite(l.test_acc) for l in logs
               if l.round % cfg.eval_every == 0)
    # nobody completed, so everyone's staleness aged one per round
    assert int(jnp.min(srv.state.staleness)) == cfg.rounds


def test_buffered_folds_and_schema_valid_log(data, tmp_path):
    path = str(tmp_path / "events.jsonl")
    mem = obs.configure(jsonl=path, memory=True)
    cfg = _cfg(churn=0.2, deadline=0.8, aggregation="buffered",
               buffer_goal=1, rounds=4)
    srv = _server(cfg, data)
    srv.run()
    for leaf in _leaves(srv.params):
        assert np.isfinite(leaf).all()
    codes = np.concatenate(srv.outcome_log)
    assert (codes == DYN.LATE).any()       # the tight deadline bites
    folds = [e for e in mem.events
             if e["kind"] == "dynamics" and e.get("name") == "buffer/fold"]
    assert folds and all(f["entries"] >= 1 for f in folds)
    errs = validate_events(load_jsonl(path), rounds=4, eval_every=1)
    assert errs == []


# ----------------------------------------------------------------------
# straggler-profile x aggregation-mode sweep (schema-valid, finite)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("profile", DYN.STRAGGLER_PROFILES)
@pytest.mark.parametrize("aggregation", ("sync", "buffered"))
def test_profile_aggregation_sweep_schema_valid(profile, aggregation,
                                                data, tmp_path):
    """Every latency profile composes with both aggregation modes: the
    run completes, params stay finite, and the event log validates."""
    path = str(tmp_path / "events.jsonl")
    obs.configure(jsonl=path, memory=True)
    cfg = _cfg(churn=0.2, deadline=0.9, rounds=3,
               straggler_profile=profile, aggregation=aggregation,
               buffer_goal=1)
    srv = _server(cfg, data)
    logs = srv.run()
    assert len(logs) == 3
    for leaf in _leaves(srv.params):
        assert np.isfinite(leaf).all()
    codes = np.concatenate(srv.outcome_log)
    assert set(np.unique(codes)) <= {DYN.NOT_SELECTED, DYN.COMPLETED,
                                     DYN.LATE, DYN.DROPPED}
    assert validate_events(load_jsonl(path), rounds=3, eval_every=1) == []


# ----------------------------------------------------------------------
# property test: fault_step key-reuse determinism (hypothesis)
# ----------------------------------------------------------------------

def test_fault_step_key_reuse_is_deterministic_property():
    """Property test (hypothesis, optional): fault_step is a pure
    function of (cfg, key, fleet arrays) — calling it twice with the
    same key yields bit-identical outcomes for arbitrary seeds, churn
    rates, deadlines and fleet sizes.  This key-reuse determinism is
    the property behind the buffered==sync oracle and the crash/resume
    bit-exactness guarantee (tests/test_checkpoint.py)."""
    pytest.importorskip(
        "hypothesis",
        reason="property tests need the optional hypothesis extra")
    import hypothesis.strategies as st
    from hypothesis import given, settings

    @given(seed=st.integers(0, 2 ** 31 - 1),
           churn=st.floats(0.0, 0.5),
           deadline=st.floats(0.1, 3.0),
           n=st.integers(4, 24))
    @settings(max_examples=20, deadline=None)
    def run(seed, churn, deadline, n):
        cfg = _cfg(num_clients=n, num_clusters=2, churn=churn,
                   deadline=deadline)
        win, avail, residual, sizes = _fleet_arrays(n)
        key = jax.random.PRNGKey(seed)
        a = DYN.fault_step(cfg, key, win, avail, residual, sizes)
        b = DYN.fault_step(cfg, key, win, avail, residual, sizes)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        out = np.asarray(a[0])
        assert (out[~np.asarray(win)] == DYN.NOT_SELECTED).all()

    run()
