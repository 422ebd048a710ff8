"""Self-healing server (ISSUE 10): adaptive adversaries
(sub_clip/alie/on_off), the auto-tuned MAD-band screen, reputation-priced
bidding, the divergence watchdog's checkpoint-ring rollback, and the
buffered-aggregation x quarantine mass fix — plus the neutrality
boundaries each of them must respect."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import FLConfig
from repro.core import aggregation as AGG
from repro.core import auction as A
from repro.core import rounds as RND
from repro.core.adapters import cnn_adapter
from repro.core.server import FederatedServer, _BufferedUpdate
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
# adaptive attack semantics (sim.dynamics)
# ----------------------------------------------------------------------

def _rows():
    rng = np.random.default_rng(0)
    deltas = jnp.asarray(rng.normal(size=(6, 8)).astype(np.float32))
    adv = jnp.array([True, False, True, False, False, False])
    valid = jnp.array([True, True, False, True, True, True])
    return deltas, adv, valid   # only row 0 is adv AND valid


def test_sub_clip_sits_under_the_clip_threshold():
    cfg = _cfg(adversary_frac=0.3, attack="sub_clip", clip_mult=2.0,
               sub_clip_margin=0.9)
    deltas, adv, valid = _rows()
    clip_ema = jnp.float32(1.7)
    out = np.asarray(DYN.corrupt_updates(cfg, jax.random.PRNGKey(1),
                                         deltas, adv, valid,
                                         clip_ema=clip_ema,
                                         round_idx=jnp.int32(0)))
    ref = np.asarray(deltas)
    np.testing.assert_array_equal(out[1:], ref[1:])   # honest untouched
    norm = float(np.linalg.norm(out[0]))
    # the malicious row's norm lands exactly at margin * clip threshold
    assert norm == pytest.approx(0.9 * 2.0 * 1.7, rel=1e-5)
    # and it pushes AGAINST the honest mean direction
    honest = ref[[1, 3, 4, 5]].mean(axis=0)
    assert float(out[0] @ honest) < 0


def test_sub_clip_falls_back_to_median_norm_when_unseeded():
    cfg = _cfg(adversary_frac=0.3, attack="sub_clip", clip_mult=2.0,
               sub_clip_margin=0.9)
    deltas, adv, valid = _rows()
    # clip EMA 0 (round 0, unseeded): target scales off the honest
    # median norm instead of a zero threshold
    out = np.asarray(DYN.corrupt_updates(cfg, jax.random.PRNGKey(1),
                                         deltas, adv, valid,
                                         clip_ema=jnp.float32(0.0),
                                         round_idx=jnp.int32(0)))
    honest_norms = np.linalg.norm(np.asarray(deltas)[[1, 3, 4, 5]], axis=1)
    # the on-device median is the lower-middle order statistic
    # (index floor((v-1)/2)), not numpy's interpolated midpoint
    med = float(np.sort(honest_norms)[1])
    assert float(np.linalg.norm(out[0])) == pytest.approx(0.9 * 2.0 * med,
                                                          rel=1e-4)


def test_alie_row_is_mean_minus_z_std():
    cfg = _cfg(adversary_frac=0.3, attack="alie", alie_z=1.5)
    deltas, adv, valid = _rows()
    out = np.asarray(DYN.corrupt_updates(cfg, jax.random.PRNGKey(1),
                                         deltas, adv, valid))
    ref = np.asarray(deltas)
    np.testing.assert_array_equal(out[1:], ref[1:])
    honest = ref[[1, 3, 4, 5]]
    expect = honest.mean(axis=0) - 1.5 * honest.std(axis=0)
    np.testing.assert_allclose(out[0], expect, rtol=1e-4, atol=1e-5)


def test_on_off_alternates_phases():
    cfg = _cfg(adversary_frac=0.3, attack="on_off", onoff_period=2,
               attack_scale=5.0)
    deltas, adv, valid = _rows()
    key = jax.random.PRNGKey(1)
    ref = np.asarray(deltas)
    for r, active in ((0, True), (1, True), (2, False), (3, False),
                      (4, True)):
        out = np.asarray(DYN.corrupt_updates(cfg, key, deltas, adv, valid,
                                             round_idx=jnp.int32(r)))
        if active:
            np.testing.assert_array_equal(out[0], 5.0 * ref[0])
        else:
            np.testing.assert_array_equal(out[0], ref[0])
        np.testing.assert_array_equal(out[1:], ref[1:])


# ----------------------------------------------------------------------
# auto-tuned screening (core.aggregation)
# ----------------------------------------------------------------------

def _screen_inputs(cfg, deltas, weights, valid, adv=None, dstate=None,
                   round_idx=0):
    cap = deltas.shape[0]
    adv = np.zeros(cap, bool) if adv is None else np.asarray(adv)
    ids = np.where(np.asarray(valid), np.arange(cap), -1).astype(np.int32)
    strikes = jnp.zeros((cfg.num_clients,), jnp.float32)
    if dstate is None:
        dstate = AGG.init_defense_state(cfg)
    return (jnp.asarray(deltas, jnp.float32),
            jnp.asarray(weights, jnp.float32), jnp.asarray(valid),
            jnp.asarray(adv), jnp.asarray(ids), strikes, dstate,
            jnp.int32(round_idx), jax.random.PRNGKey(0))


def _tight_cohort(attacker_norm=5.0):
    """8 honest rows with tightly-spread norms ~1 plus one attacker row
    at ``attacker_norm`` — inside a loose static clip threshold, far
    outside the honest MAD band."""
    rng = np.random.default_rng(5)
    deltas = rng.normal(size=(9, 16)).astype(np.float32)
    deltas /= np.linalg.norm(deltas, axis=1, keepdims=True)
    deltas[1:] *= rng.uniform(0.95, 1.05, size=(8, 1)).astype(np.float32)
    deltas[0] *= attacker_norm
    w = np.full(9, 1 / 9, np.float32)
    return deltas, w, np.ones(9, bool)


def test_adaptive_band_catches_sub_threshold_outlier():
    # static clip with a loose multiplier lets a 5x-median row through
    # with only norm-clipping... at clip_mult=8 it is not even clipped
    deltas, w, valid = _tight_cohort(attacker_norm=5.0)
    cfg_s = _cfg(defense="clip", clip_mult=8.0, defense_mode="static")
    _, strikes_s, _, rep_s = AGG.make_screened_step(cfg_s)(
        *_screen_inputs(cfg_s, deltas, w, valid))
    assert int(rep_s["num_screened"]) == 0
    assert float(rep_s["clipped_frac"]) == 0.0
    assert not np.asarray(strikes_s).any()

    # ...the adaptive band excludes it outright and strikes the sender
    cfg_a = _cfg(defense="clip", clip_mult=8.0, defense_mode="adaptive",
                 outlier_strike=0.5)
    # seed the running stats so round-0 has a band to screen against
    ds = AGG.DefenseState(clip_ema=jnp.float32(1.0),
                          mad_ema=jnp.float32(0.02),
                          pressure=jnp.float32(0.0), tighten=None)
    agg, strikes_a, ds2, rep_a = AGG.make_screened_step(cfg_a)(
        *_screen_inputs(cfg_a, deltas, w, valid, dstate=ds))
    assert int(rep_a["num_screened"]) == 1
    assert int(rep_a["num_survivors"]) == 8
    s = np.asarray(strikes_a)
    assert s[0] == pytest.approx(0.5) and s.sum() == pytest.approx(0.5)
    # the excluded row carries no weight in the aggregate
    assert float(np.linalg.norm(np.asarray(agg))) < 2.0
    # rejection raised the pressure EMA, which tightens the next band
    assert float(ds2.pressure) > 0.0
    assert float(rep_a["defense_pressure"]) == pytest.approx(
        float(ds2.pressure))


def test_adaptive_band_admits_clean_cohort():
    deltas, w, valid = _tight_cohort(attacker_norm=1.0)   # no outlier
    cfg = _cfg(defense="clip", defense_mode="adaptive")
    ds = AGG.DefenseState(clip_ema=jnp.float32(1.0),
                          mad_ema=jnp.float32(0.02),
                          pressure=jnp.float32(0.0), tighten=None)
    _, strikes, ds2, rep = AGG.make_screened_step(cfg)(
        *_screen_inputs(cfg, deltas, w, valid, dstate=ds))
    assert int(rep["num_screened"]) == 0
    assert int(rep["num_survivors"]) == 9
    assert not np.asarray(strikes).any()
    assert float(rep["survivor_frac"]) == 1.0
    # pressure decays toward zero on clean rounds
    assert float(ds2.pressure) <= float(ds.pressure)


def test_pressure_tightens_k_eff():
    # same cohort, same stats: a borderline outlier survives at zero
    # pressure and is screened once the pressure EMA is high
    deltas, w, valid = _tight_cohort(attacker_norm=1.3)
    cfg = _cfg(defense="clip", clip_mult=8.0, defense_mode="adaptive",
               adapt_k=3.0, adapt_gain=4.0)
    screen = AGG.make_screened_step(cfg)
    ds_lo = AGG.DefenseState(clip_ema=jnp.float32(1.0),
                             mad_ema=jnp.float32(0.2),
                             pressure=jnp.float32(0.0), tighten=None)
    ds_hi = AGG.DefenseState(clip_ema=jnp.float32(1.0),
                             mad_ema=jnp.float32(0.2),
                             pressure=jnp.float32(1.0), tighten=None)
    _, _, _, rep_lo = screen(*_screen_inputs(cfg, deltas, w, valid,
                                             dstate=ds_lo))
    _, _, _, rep_hi = screen(*_screen_inputs(cfg, deltas, w, valid,
                                             dstate=ds_hi))
    assert int(rep_lo["num_screened"]) == 0
    assert int(rep_hi["num_screened"]) == 1


def test_static_mode_trace_unchanged_by_adaptive_knobs():
    # defense_mode='static' must ignore every adaptive knob: identical
    # aggregates, strikes and clip EMA vs a default-knob config
    deltas, w, valid = _tight_cohort(attacker_norm=5.0)
    cfg1 = _cfg(defense="clip")
    cfg2 = _cfg(defense="clip", adapt_k=0.1, adapt_gain=99.0,
                outlier_strike=7.0)
    o1 = AGG.make_screened_step(cfg1)(*_screen_inputs(cfg1, deltas, w,
                                                      valid))
    o2 = AGG.make_screened_step(cfg2)(*_screen_inputs(cfg2, deltas, w,
                                                      valid))
    np.testing.assert_array_equal(np.asarray(o1[0]), np.asarray(o2[0]))
    np.testing.assert_array_equal(np.asarray(o1[1]), np.asarray(o2[1]))
    assert float(o1[2].clip_ema) == float(o2[2].clip_ema)
    assert o1[2].mad_ema is None and o2[2].mad_ema is None


# ----------------------------------------------------------------------
# reputation-priced bidding (core.auction / schemes)
# ----------------------------------------------------------------------

def test_effective_bids_identity_in_ban_mode():
    cfg = _cfg(adversary_frac=0.3, attack="nan", defense="median")
    bids = jnp.array([0.1, 0.2, 0.3])
    strikes = jnp.array([5.0, 0.0, 0.0])
    assert A.effective_bids(bids, strikes, cfg) is bids     # same object
    assert A.effective_bids(bids, None, cfg) is bids


def test_effective_bids_price_inflation_preserves_inf():
    cfg = _cfg(adversary_frac=0.3, attack="nan", defense="median",
               reputation_mode="price", rep_price_gain=2.0)
    bids = jnp.array([0.1, 0.2, float(A.INF)])
    strikes = jnp.array([3.0, 0.0, 0.0])
    eff = np.asarray(A.effective_bids(bids, strikes, cfg))
    assert eff[0] == pytest.approx(0.1 * 7.0)   # 1 + 2*3
    assert eff[1] == pytest.approx(0.2)         # clean: true bid
    assert eff[2] == float(A.INF)               # ineligible stays INF


def test_price_mode_flips_auction_winner():
    cfg = _cfg(reputation_mode="price", rep_price_gain=1.0)
    clusters = jnp.zeros(4, jnp.int32)
    eligible = jnp.ones(4, bool)
    bids = jnp.array([0.10, 0.15, 0.30, 0.40])
    tie = jnp.zeros(4)
    strikes = jnp.array([2.0, 0.0, 0.0, 0.0])   # cheapest client tainted
    win_true = np.asarray(A.cluster_winners(bids, clusters, eligible, 1,
                                            cfg.num_clusters,
                                            tie_break=tie))
    win_eff = np.asarray(A.cluster_winners(
        A.effective_bids(bids, strikes, cfg), clusters, eligible, 1,
        cfg.num_clusters, tie_break=tie))
    assert win_true[0] and not win_true[1]
    # 0.10 * (1 + 2) = 0.30 ties client 2's true bid; client 1 at 0.15
    # is now the cheapest effective bid
    assert win_eff[1] and not win_eff[0]


def test_ban_mode_run_bit_identical_to_pre_pricing(data):
    # reputation_mode='ban' (default) must reproduce the PR 8 strike/ban
    # behavior bit-exactly even though the pricing hook is in the trace
    cfg = _cfg(rounds=6, adversary_frac=0.3, attack="nan",
               defense="median", strike_threshold=1.0, strike_decay=1.0)
    srv = _server(cfg, data)
    adv = np.asarray(obs.device_get(DYN.adversary_mask(cfg)), bool)
    logs = srv.run(rounds=6)
    strikes = np.asarray(obs.device_get(srv.state.strikes))
    assert (strikes[~adv] == 0).all()
    banned_at = {}
    for log in logs:
        for c in log.selected:
            assert int(c) not in banned_at
        for c in log.selected:
            if adv[int(c)]:
                banned_at.setdefault(int(c), log.round + 1)
    assert banned_at    # the ban machinery actually engaged


def test_price_mode_keeps_struck_clients_biddable(data):
    # same attack, price mode: no hard ban — a struck adversary can
    # still appear in later selections (priced, not excluded)
    cfg = _cfg(rounds=6, adversary_frac=0.3, attack="nan",
               defense="median", strike_threshold=1.0, strike_decay=1.0,
               reputation_mode="price", rep_price_gain=0.1)
    srv = _server(cfg, data)
    adv = np.asarray(obs.device_get(DYN.adversary_mask(cfg)), bool)
    logs = srv.run(rounds=6)
    strikes = np.asarray(obs.device_get(srv.state.strikes))
    assert (strikes[~adv] == 0).all()          # honest never struck
    struck_then_selected = False
    seen_struck = set()
    for log in logs:
        for c in log.selected:
            if int(c) in seen_struck:
                struck_then_selected = True
        for c in log.selected:
            if adv[int(c)]:
                seen_struck.add(int(c))
    assert struck_then_selected    # ban mode would have excluded them


# ----------------------------------------------------------------------
# neutrality property: adversary_frac 0 => trust constant, selection
# bit-identical to defense-off — both runtimes + the scan fast path
# ----------------------------------------------------------------------

@pytest.mark.parametrize("runtime", RUNTIMES)
@pytest.mark.parametrize("rep_mode", ("ban", "price"))
def test_frac0_trust_constant_and_selection_identical(runtime, rep_mode,
                                                      data):
    plain = _server(_cfg(runtime=runtime, rounds=3), data)
    logs_p = plain.run(rounds=3)
    srv = _server(_cfg(runtime=runtime, rounds=3, adversary_frac=0.0,
                       attack="none", defense="median",
                       reputation_mode=rep_mode), data)
    assert srv.defended                        # strikes ledger active
    logs_d = srv.run(rounds=3)
    # no clean client's trust ever decreases: zero strikes throughout
    strikes = np.asarray(obs.device_get(srv.state.strikes))
    assert (strikes == 0).all()
    # selection stays bit-identical to the defense-off run (the trust
    # gate / bid pricing are exact no-ops at zero strikes)
    for lp, ld in zip(logs_p, logs_d):
        np.testing.assert_array_equal(lp.selected, ld.selected)
        assert lp.mean_bid == ld.mean_bid


@pytest.mark.parametrize("runtime", RUNTIMES)
@pytest.mark.parametrize("rep_mode", ("ban", "price"))
def test_frac0_adaptive_strikes_only_band_outliers(runtime, rep_mode,
                                                   data):
    # the adaptive band screens norm outliers among honest clients too
    # (a non-IID fleet's norms spread), so at adversary_frac 0 trust is
    # not constant; what holds is that nothing is quarantined, every
    # strike is a band exclusion's, and the first auction (before any
    # screen ran) matches the defense-off run
    cfg = _cfg(runtime=runtime, rounds=3, adversary_frac=0.0,
               attack="none", defense="median", defense_mode="adaptive",
               reputation_mode=rep_mode)
    plain = _server(_cfg(runtime=runtime, rounds=3), data)
    logs_p = plain.run(rounds=3)
    srv = _server(cfg, data)
    logs_d = srv.run(rounds=3)
    strikes = np.asarray(obs.device_get(srv.state.strikes))
    screened = srv.defense_totals["screened"]
    assert srv.defense_totals["quarantined"] == 0
    assert (strikes >= 0).all()
    assert strikes.any() == (screened > 0)
    assert int((strikes > 0).sum()) <= screened
    assert strikes.sum() <= cfg.outlier_strike * screened + 1e-6
    np.testing.assert_array_equal(logs_p[0].selected, logs_d[0].selected)
    assert logs_p[0].mean_bid == logs_d[0].mean_bid


@pytest.mark.parametrize("rep_mode", ("ban", "price"))
def test_frac0_scan_fast_path_identical(rep_mode):
    import dataclasses
    cfg = _cfg(num_clients=64, num_clusters=4, reputation_mode=rep_mode)
    key = jax.random.PRNGKey(11)
    state0 = RND.synthetic_fleet(cfg, key)
    kr = jax.random.fold_in(key, 1)
    _, m_plain, w_plain = RND.simulate_rounds(state0, cfg, kr, 5,
                                              record_wins=True)
    state_s = dataclasses.replace(
        state0, strikes=jnp.zeros((cfg.num_clients,), jnp.float32))
    final, m_def, w_def = RND.simulate_rounds(state_s, cfg, kr, 5,
                                              record_wins=True)
    np.testing.assert_array_equal(np.asarray(w_plain), np.asarray(w_def))
    np.testing.assert_array_equal(np.asarray(m_plain["mean_bid"]),
                                  np.asarray(m_def["mean_bid"]))
    # trust stayed 1.0 every round (strikes never grow without a screen)
    assert np.asarray(m_def["trust_min"]).min() == 1.0
    assert (np.asarray(obs.device_get(final.strikes)) == 0).all()


# optional hypothesis sweep over seeds (repo convention: skip without
# the extra — tests/test_clustering.py does the same)
try:
    import hypothesis  # noqa: F401
    import hypothesis.strategies as st
    from hypothesis import given, settings

    @settings(max_examples=5, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2 ** 16))
    def test_property_frac0_scan_trust_never_decreases(seed):
        import dataclasses
        cfg = _cfg(num_clients=32, num_clusters=4, seed=int(seed) % 97,
                   reputation_mode="price")
        key = jax.random.PRNGKey(int(seed))
        state0 = RND.synthetic_fleet(cfg, key)
        kr = jax.random.fold_in(key, 1)
        state_s = dataclasses.replace(
            state0, strikes=jnp.zeros((cfg.num_clients,), jnp.float32))
        _, m_plain, w_plain = RND.simulate_rounds(state0, cfg, kr, 4,
                                                  record_wins=True)
        final, m_def, w_def = RND.simulate_rounds(state_s, cfg, kr, 4,
                                                  record_wins=True)
        np.testing.assert_array_equal(np.asarray(w_plain),
                                      np.asarray(w_def))
        assert np.asarray(m_def["trust_min"]).min() == 1.0
except ImportError:
    pass


# ----------------------------------------------------------------------
# divergence watchdog (core.server)
# ----------------------------------------------------------------------

def test_watchdog_rolls_back_nan_storm_and_run_completes(data, tmp_path):
    path = str(tmp_path / "wd.jsonl")
    obs.OBS.configure(jsonl=path, memory=True)
    cfg = _cfg(rounds=4, eval_every=1, adversary_frac=0.3, attack="nan",
               defense="none", watchdog="on", watchdog_ring=3)
    srv = _server(cfg, data)
    logs = srv.run(rounds=4)
    obs.OBS.flush()
    # the run finished every round despite params going non-finite...
    assert [l.round for l in logs] == [0, 1, 2, 3]
    assert srv.watchdog_totals["rollbacks"] >= 1
    # ...and the final params are the restored healthy snapshot
    for lf in _leaves(srv.params):
        assert np.isfinite(lf).all()
    events = load_jsonl(path)
    rb = [e for e in events if e.get("kind") == "watchdog"
          and e.get("name") == "rollback"]
    assert rb and all(isinstance(e.get("reason"), str) for e in rb)
    assert rb[0]["reason"] == "non_finite_eval"
    assert validate_events(events, rounds=4, eval_every=1,
                           min_rollbacks=1) == []


def test_watchdog_rollback_decays_lr_and_tightens(data):
    cfg = _cfg(rounds=3, eval_every=1, adversary_frac=0.3, attack="nan",
               defense="clip", clip_mult=1e9, watchdog="on",
               watchdog_lr_decay=0.5, watchdog_tighten=2.0)
    srv = _server(cfg, data)
    assert float(srv._srv_lr) == 1.0
    ds0 = srv._defense_state
    assert float(ds0.tighten) == 1.0           # watchdog threads tighten
    srv._wd_snapshot(-1)
    srv._wd_rollback("loss_spike", 0)
    assert float(srv._srv_lr) == 0.5
    assert float(srv._defense_state.tighten) == 2.0
    srv._wd_rollback("loss_spike", 1)
    assert float(srv._srv_lr) == 0.25
    assert float(srv._defense_state.tighten) == 4.0


def test_watchdog_on_clean_run_bit_identical_to_off(data):
    # no rollback ever fires on a clean run, and the server-LR hooks are
    # exact no-ops at lr=1.0 — params and selections match bit-for-bit
    off = _server(_cfg(rounds=3), data)
    logs_off = off.run(rounds=3)
    on = _server(_cfg(rounds=3, watchdog="on"), data)
    logs_on = on.run(rounds=3)
    assert on.watchdog_totals["rollbacks"] == 0
    assert on.watchdog_totals["snapshots"] >= 1
    _assert_trees_equal(off.params, on.params)
    for lo, ln in zip(logs_off, logs_on):
        np.testing.assert_array_equal(lo.selected, ln.selected)
        assert lo.mean_bid == ln.mean_bid
        assert lo.test_acc == pytest.approx(ln.test_acc, nan_ok=True)


def test_watchdog_defended_clean_run_bit_identical_to_off(data):
    # same boundary through the DEFENDED path: the screen carries a
    # tighten factor (1.0) and the delta scales by srv_lr (1.0) — both
    # exact identities until a rollback actually fires
    cfg_off = _cfg(rounds=3, adversary_frac=0.3, attack="scale",
                   defense="trimmed")
    off = _server(cfg_off, data)
    off.run(rounds=3)
    on = _server(_cfg(rounds=3, adversary_frac=0.3, attack="scale",
                      defense="trimmed", watchdog="on"), data)
    on.run(rounds=3)
    assert on.watchdog_totals["rollbacks"] == 0
    _assert_trees_equal(off.params, on.params)
    np.testing.assert_array_equal(
        np.asarray(obs.device_get(off.state.strikes)),
        np.asarray(obs.device_get(on.state.strikes)))


def test_watchdog_checkpoint_roundtrip(data, tmp_path):
    # defense_state + server_lr ride the checkpoint tree; a resumed
    # watchdog run continues from the restored values
    cfg = _cfg(rounds=4, adversary_frac=0.3, attack="scale",
               defense="clip", defense_mode="adaptive", watchdog="on")
    path = str(tmp_path / "wd_ck")
    ref = _server(cfg, data)
    ref.run(rounds=4)
    crashed = _server(cfg, data)
    crashed.run(rounds=3, checkpoint_every=2, checkpoint_path=path)
    resumed = _server(cfg, data)
    resumed.run(rounds=4, checkpoint_path=path, resume=True)
    _assert_trees_equal(ref.params, resumed.params)
    assert float(ref._defense_state.clip_ema) == float(
        resumed._defense_state.clip_ema)
    assert float(ref._defense_state.pressure) == float(
        resumed._defense_state.pressure)
    assert float(ref._srv_lr) == float(resumed._srv_lr)


# ----------------------------------------------------------------------
# buffered aggregation x quarantine (satellite fix)
# ----------------------------------------------------------------------

def _dyn_buffered_cfg(**kw):
    base = dict(rounds=4, churn=0.0, deadline=1.1, aggregation="buffered",
                buffer_goal=1, buffer_timeout=1, adversary_frac=0.3,
                attack="nan", defense="median")
    base.update(kw)
    return _cfg(**base)


def test_fully_quarantined_late_cohort_folds_zero_mass(data):
    mem = obs.OBS.configure(memory=True)
    srv = _server(_dyn_buffered_cfg(), data)
    params0 = srv.params
    # a parked late update whose every row was quarantined: survivor
    # fraction 0 -> the fold must drop it, not divide 0/0 or pull the
    # params toward the (zeroed) delta
    poisoned = jax.tree.map(jnp.ones_like, srv.params)
    srv._late_buffer.append(_BufferedUpdate(
        delta=poisoned, mass=500.0, round=0, arrival=1,
        mass_scale=jnp.float32(0.0)))
    folded = srv._maybe_fold_buffer(2, force=True)
    assert folded == 0
    assert srv._late_buffer == []              # dropped, not retried
    _assert_trees_equal(params0, srv.params)   # params untouched
    # the drop is loud: counter + dynamics event mark it for the schema
    assert obs.OBS.counters.get("dyn/buffer_all_quarantined", 0) == 1
    obs.OBS.flush()
    names = [e.get("name") for e in mem.events
             if e.get("kind") == "dynamics"]
    assert "buffer/all_quarantined" in names


def test_partially_quarantined_late_cohort_scales_mass(data):
    srv = _server(_dyn_buffered_cfg(), data)
    params0 = srv.params
    ones = jax.tree.map(jnp.ones_like, srv.params)
    # two entries, equal raw mass: one fully screened out, one intact —
    # the fold weight must come ONLY from the intact entry
    srv._late_buffer.append(_BufferedUpdate(
        delta=ones, mass=100.0, round=1, arrival=2,
        mass_scale=jnp.float32(0.0)))
    srv._late_buffer.append(_BufferedUpdate(
        delta=ones, mass=100.0, round=1, arrival=2,
        mass_scale=jnp.float32(1.0)))
    folded = srv._maybe_fold_buffer(2, force=True)
    assert folded == 2
    w = DYN.staleness_weight(srv.cfg, 1)
    for a, b in zip(_leaves(params0), _leaves(srv.params)):
        np.testing.assert_allclose(b, a + float(w), rtol=1e-6)


def test_buffered_defended_run_stays_finite(data):
    # end-to-end: NaN adversaries + deadline misses + buffered folds —
    # the defended fold path must never push non-finite params
    srv = _server(_dyn_buffered_cfg(rounds=4), data)
    logs = srv.run(rounds=4)
    assert len(logs) == 4
    for lf in _leaves(srv.params):
        assert np.isfinite(lf).all()


# ----------------------------------------------------------------------
# compile-once: adaptive screen + watchdog keep the warm loop trace-free
# ----------------------------------------------------------------------

def test_device_selfheal_warm_loop_zero_retrace(data):
    cfg = _cfg(runtime="device", rounds=8, adversary_frac=0.3,
               attack="sub_clip", defense="clip",
               defense_mode="adaptive", reputation_mode="price",
               watchdog="on")
    srv = _server(cfg, data)
    base = obs.jax_stats.snapshot()
    srv.run(rounds=3)
    snap = obs.jax_stats.snapshot()
    assert obs.jax_stats.delta(base).get("traces/screened_agg") == 1
    with obs.sync_audit():                 # no implicit host transfers
        for t in range(3, 8):              # shifting cohorts, warm
            srv._dispatch_round(t, eval_now=False)
    srv._flush_pending()
    d = obs.jax_stats.delta(snap)
    retraces = {k: v for k, v in d.items() if k.startswith("traces")}
    assert not retraces, retraces
