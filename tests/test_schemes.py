"""Selection-scheme registry (repro.core.schemes): refactor neutrality
(the 'paper' scheme through the scheme interface is bit-identical to the
pre-registry control plane), per-scheme semantics (fedcs never picks a
deadline-infeasible winner, longterm budget monotonicity, random matches
its reference sampler under the same key chain), zero warm retraces for
every scheme on the scan fast path, and the obs schema's scheme-tagged
scalar rules."""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import FLConfig
from repro.core import rounds as RND
from repro.core import schemes as SCH
from repro.core import selection as SEL
from repro.obs import schema as SCHEMA

ALL_SCHEMES = ("paper", "random", "fedcs", "longterm_auction")


@pytest.fixture(autouse=True)
def _obs_reset():
    obs.OBS.reset()
    yield
    obs.OBS.reset()


def _cfg(**kw):
    base = dict(num_clients=60, num_clusters=5, select_ratio=0.2, seed=0)
    base.update(kw)
    return FLConfig(**base)


def _fleet(cfg, seed=0):
    return RND.synthetic_fleet(cfg, jax.random.PRNGKey(seed))


# ----------------------------------------------------------------------
# registry basics
# ----------------------------------------------------------------------

def test_registry_lists_the_zoo():
    assert set(ALL_SCHEMES) <= set(SCH.scheme_names())


def test_unknown_scheme_errors_with_names():
    with pytest.raises(KeyError, match="registered schemes"):
        SCH.get_scheme("definitely_not_a_scheme")


def test_duplicate_registration_rejected():
    with pytest.raises(ValueError, match="already registered"):
        SCH.register(SCH.get_scheme("paper"))


def test_scheme_state_init_shapes():
    cfg = _cfg(scheme_select="longterm_auction")
    ss = SCH.init_scheme_state(cfg)
    assert isinstance(ss, SCH.LongTermState)
    assert ss.paid.shape == (cfg.num_clients,)
    assert float(ss.spent) == 0.0 and float(ss.queue) == 0.0
    for name in ("paper", "random", "fedcs"):
        assert SCH.init_scheme_state(_cfg(scheme_select=name)) is None


# ----------------------------------------------------------------------
# refactor neutrality: 'paper' == the pre-registry control plane
# ----------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg",))
def _pre_registry_round(state, key, cfg):
    """The control-plane round exactly as the pre-registry _round_body
    computed it (select_round -> rewards -> energy/history update, with
    the strikes trust gate composed upstream) — the neutrality oracle."""
    avail = None
    if state.strikes is not None:
        avail = state.strikes < cfg.strike_threshold
    win, info = SEL.select_round(state, cfg, key, avail=avail)
    client_r, server_r = RND.round_rewards(win, info["bids"],
                                           state.local_sizes, cfg)
    return SEL.update_after_round(state, win, cfg), win, client_r


@pytest.mark.parametrize("scheme", ["gradient_cluster_auction",
                                    "gradient_cluster_random", "random"])
def test_paper_scheme_bit_identical_to_pre_registry(scheme):
    cfg = _cfg(scheme=scheme, scheme_select="paper")
    state = _fleet(cfg)
    key = jax.random.PRNGKey(7)
    for _ in range(4):
        key, k = jax.random.split(key)
        ref_state, ref_win, _ = _pre_registry_round(state, k, cfg)
        new_state, win, metrics = RND._round_step_jit(
            state, k, None, None, cfg, "segmented")
        np.testing.assert_array_equal(np.asarray(win), np.asarray(ref_win))
        np.testing.assert_array_equal(np.asarray(new_state.residual),
                                      np.asarray(ref_state.residual))
        np.testing.assert_array_equal(np.asarray(new_state.history),
                                      np.asarray(ref_state.history))
        assert new_state.scheme_state is None
        state = new_state


def test_paper_scheme_bit_identical_with_strikes():
    # the defended state (strikes ledger) rides the same neutrality rule
    cfg = _cfg(scheme_select="paper", defense="median")
    state = _fleet(cfg)
    strikes = jnp.zeros((cfg.num_clients,), jnp.float32).at[3].set(5.0)
    state = dataclasses.replace(state, strikes=strikes)
    key = jax.random.PRNGKey(11)
    ref_state, ref_win, _ = _pre_registry_round(state, key, cfg)
    new_state, win, metrics = RND._round_step_jit(
        state, key, None, None, cfg, "segmented")
    np.testing.assert_array_equal(np.asarray(win), np.asarray(ref_win))
    np.testing.assert_array_equal(np.asarray(new_state.strikes),
                                  np.asarray(ref_state.strikes))
    assert not bool(np.asarray(win)[3])      # banned client never wins
    assert int(metrics["num_banned"]) == 1


def test_paper_scan_matches_reference_oracle():
    # the scan fast path and the eager per-round reference stay the
    # bit-identity pair under the scheme dispatch
    cfg = _cfg(scheme_select="paper")
    state = _fleet(cfg)
    key = jax.random.PRNGKey(3)
    _, m_scan, w_scan = RND.simulate_rounds(state, cfg, key, 5,
                                            record_wins=True)
    _, m_ref, w_ref = RND.simulate_rounds_reference(state, cfg, key, 5,
                                                    record_wins=True)
    np.testing.assert_array_equal(np.asarray(w_scan), w_ref)
    for k in m_ref:
        np.testing.assert_array_equal(np.asarray(m_scan[k]), m_ref[k])


@pytest.mark.parametrize("scheme", ALL_SCHEMES)
def test_scan_matches_reference_for_every_scheme(scheme):
    cfg = _cfg(scheme_select=scheme)
    state = _fleet(cfg)
    key = jax.random.PRNGKey(5)
    _, m_scan, w_scan = RND.simulate_rounds(state, cfg, key, 4,
                                            record_wins=True)
    _, m_ref, w_ref = RND.simulate_rounds_reference(state, cfg, key, 4,
                                                    record_wins=True)
    np.testing.assert_array_equal(np.asarray(w_scan), w_ref)
    for k in m_ref:
        np.testing.assert_array_equal(np.asarray(m_scan[k]), m_ref[k],
                                      err_msg=k)


# ----------------------------------------------------------------------
# zero warm retraces: every scheme compiles into ONE scan program
# ----------------------------------------------------------------------

@pytest.mark.parametrize("scheme", ALL_SCHEMES)
def test_scan_zero_warm_retraces(scheme):
    cfg = _cfg(scheme_select=scheme)
    state = _fleet(cfg)
    key = jax.random.PRNGKey(9)
    out = RND.simulate_rounds(state, cfg, key, 3)
    jax.block_until_ready(out[1])
    snap = obs.jax_stats.snapshot()
    out = RND.simulate_rounds(state, cfg, key, 3)
    jax.block_until_ready(out[1])
    d = obs.jax_stats.delta(snap)
    traces = {k: v for k, v in d.items() if k.startswith("traces")}
    assert not traces, f"warm scan retraced under {scheme!r}: {traces}"


# ----------------------------------------------------------------------
# per-scheme semantics
# ----------------------------------------------------------------------

def test_random_matches_reference_sampler_under_same_key_chain():
    cfg = _cfg(scheme_select="random")
    state = _fleet(cfg)
    avail = jnp.arange(cfg.num_clients) % 3 != 0     # some offline
    key = jax.random.PRNGKey(13)
    win, info = SCH.random_select(state, cfg, key, avail=avail)
    # the oracle consumes keys[1] of the same 4-way split and applies
    # the same post-pick hard availability mask
    keys = jax.random.split(key, 4)
    ref = SEL._random_per_cluster_loop(keys[1], state, cfg, avail) & avail
    np.testing.assert_array_equal(np.asarray(win), np.asarray(ref))
    assert not np.asarray(win & ~avail).any()
    assert float(info["bids"].sum()) == 0.0


def test_fedcs_never_picks_infeasible_winner():
    cfg = _cfg(scheme_select="fedcs", fedcs_deadline=1.0,
               init_energy_mode="normal")
    state = _fleet(cfg, seed=2)
    for s in range(5):
        key = jax.random.PRNGKey(100 + s)
        win, info = SCH.fedcs_select(state, cfg, key)
        lat = np.asarray(info["pred_latency"])
        w = np.asarray(win)
        assert (lat[w] <= SCH.fedcs_deadline(cfg)).all()
        # the prediction is deterministic given (key, state)
        lat2 = np.asarray(SCH.fedcs_predicted_latency(state, cfg, key))
        np.testing.assert_array_equal(lat, lat2)


def test_fedcs_deadline_prefers_enforced_deadline():
    assert SCH.fedcs_deadline(_cfg(deadline=1.2, fedcs_deadline=9.0)) == 1.2
    assert SCH.fedcs_deadline(_cfg(deadline=0.0, fedcs_deadline=9.0)) == 9.0


def test_fedcs_gating_is_a_strict_subset_of_paper():
    # feasibility only removes winners relative to an infinite deadline
    cfg_loose = _cfg(scheme_select="fedcs", fedcs_deadline=1e9)
    cfg_tight = cfg_loose.replace(fedcs_deadline=0.8)
    state = _fleet(cfg_loose, seed=4)
    key = jax.random.PRNGKey(21)
    win_loose, _ = SCH.fedcs_select(state, cfg_loose, key)
    win_paper, _ = SEL.select_round(state, cfg_loose, key)
    np.testing.assert_array_equal(np.asarray(win_loose),
                                  np.asarray(win_paper))
    win_tight, info = SCH.fedcs_select(state, cfg_tight, key)
    assert int(win_tight.sum()) <= int(win_loose.sum())


def test_longterm_budget_monotone_and_queue_nonnegative():
    cfg = _cfg(scheme_select="longterm_auction", total_reward=20.0,
               target_rounds=10)
    state = _fleet(cfg)
    _, m, _ = RND.simulate_rounds(state, cfg, jax.random.PRNGKey(1), 30)
    m = jax.device_get(m)
    remaining = np.asarray(m["budget_remaining"])
    assert (np.diff(remaining) <= 1e-5).all()        # spent is monotone
    assert (np.asarray(m["budget_queue"]) >= 0.0).all()
    assert (np.asarray(m["budget_spent"]) >= 0.0).all()
    # per-round spend is exactly the reward model's client payout
    np.testing.assert_allclose(np.asarray(m["budget_spent"]),
                               np.asarray(m["client_reward_sum"]),
                               rtol=1e-6)


def test_longterm_exhausted_budget_selects_no_one():
    cfg = _cfg(scheme_select="longterm_auction", total_reward=1.0,
               target_rounds=100)
    state = _fleet(cfg)
    ss = SCH.LongTermState(spent=jnp.float32(1.5), queue=jnp.float32(0.0),
                           paid=jnp.zeros((cfg.num_clients,), jnp.float32))
    state = dataclasses.replace(state, scheme_state=ss)
    win, _ = SCH.longterm_select(state, cfg, jax.random.PRNGKey(0))
    assert int(win.sum()) == 0


def test_longterm_backlog_caps_admissible_bids():
    cfg = _cfg(scheme_select="longterm_auction")
    state = _fleet(cfg, seed=6)
    key = jax.random.PRNGKey(2)
    # huge backlog -> cap near 0 -> nobody's Nash bid is admissible
    per_round = cfg.total_reward / cfg.target_rounds
    ss = SCH.LongTermState(spent=jnp.float32(0.0),
                           queue=jnp.float32(1e6 * per_round),
                           paid=jnp.zeros((cfg.num_clients,), jnp.float32))
    st = dataclasses.replace(state, scheme_state=ss)
    win, _ = SCH.longterm_select(st, cfg, key)
    assert int(win.sum()) == 0
    # zero backlog -> cap 1.0 -> identical to the paper's auction (bids
    # are clipped into [0, 1], so the cap is a no-op)
    st0 = dataclasses.replace(state,
                              scheme_state=SCH._longterm_init(cfg))
    win0, _ = SCH.longterm_select(st0, cfg, key)
    ref, _ = SEL.select_round(state, cfg, key)
    np.testing.assert_array_equal(np.asarray(win0), np.asarray(ref))


def test_longterm_without_state_raises():
    cfg = _cfg(scheme_select="longterm_auction")
    state = _fleet(_cfg(scheme_select="paper"))     # scheme_state=None
    with pytest.raises(ValueError, match="needs scheme_state"):
        SCH.longterm_select(state, cfg, jax.random.PRNGKey(0))


def test_host_replacement_mask_fedcs_only():
    sizes = np.array([100, 5000, 300], np.int64)
    assert SCH.host_replacement_mask(_cfg(), sizes) is None
    m = SCH.host_replacement_mask(
        _cfg(scheme_select="fedcs", fedcs_deadline=1.0), sizes)
    assert m is not None and m.dtype == bool
    assert not m[1]      # the outsized client can't plausibly make it


# ----------------------------------------------------------------------
# obs schema: scheme-tagged scalar series
# ----------------------------------------------------------------------

def test_schema_stateful_schemes_mirror_the_registry():
    assert tuple(SCHEMA.STATEFUL_SCHEMES) == SCH.stateful_scheme_names()


def _round_rows(rows):
    evs = [{"kind": "meta", "ts": 0.0}]
    for r, extra in enumerate(rows):
        e = {"kind": "round", "ts": float(r + 1), "round": r,
             "test_acc": 0.5, "test_loss": 1.0, "energy_std": 0.1,
             "mean_bid": 0.2, "vds_gap": 0.0}
        e.update(extra)
        evs.append(e)
    return evs


def test_schema_accepts_scheme_tagged_stream():
    evs = _round_rows([{"fairness_hist_std": 0.3, "budget_spent": 1.0,
                        "budget_remaining": 9.0, "budget_queue": 0.0}] * 3)
    assert SCHEMA.validate_events(evs,
                                  scheme_select="longterm_auction") == []
    assert SCHEMA.validate_events(evs, scheme_select="paper") == []


def test_schema_rejects_stateful_scheme_without_budget_scalars():
    evs = _round_rows([{"fairness_hist_std": 0.3}] * 2)
    errs = SCHEMA.validate_events(evs, scheme_select="longterm_auction")
    assert errs and any("budget_spent" in e for e in errs)
    # …but the same stream is fine for a stateless scheme
    assert SCHEMA.validate_events(evs, scheme_select="fedcs") == []


def test_schema_rejects_missing_fairness_scalar():
    evs = _round_rows([{}] * 2)
    errs = SCHEMA.validate_events(evs, scheme_select="paper")
    assert errs and any("fairness_hist_std" in e for e in errs)
    # without a scheme tag the stream validates as before
    assert SCHEMA.validate_events(evs) == []


# ----------------------------------------------------------------------
# server integration: neutrality across runtimes + scheme metric drain
# ----------------------------------------------------------------------

RUNTIMES = ("sequential", "device")


@pytest.fixture(scope="module")
def _mnist():
    from repro.data.synthetic import make_image_dataset
    return make_image_dataset("mnist", n_train=700, n_test=120, seed=3)


def _run_server(data, rounds=3, **kw):
    from repro.core.adapters import cnn_adapter
    from repro.core.server import FederatedServer
    from repro.data.partition import partition_clients
    train, test = data
    base = dict(num_clients=10, num_clusters=3, select_ratio=0.4,
                rounds=rounds, sample_window=10, cluster_resamples=2,
                init_energy_mode="normal", seed=3)
    base.update(kw)
    cfg = FLConfig(**base)
    clients = partition_clients(train.y, cfg, seed=3)
    srv = FederatedServer(cfg, cnn_adapter("mnist"), train.x, train.y,
                          clients, {"x": test.x[:64], "y": test.y[:64]})
    logs = srv.run(rounds=rounds)
    return srv, logs


def test_paper_scheme_neutral_across_all_runtimes(_mnist):
    # the control plane is runtime-independent: the paper scheme through
    # the registry produces identical selections, residual energy and
    # participation history on every cohort execution backend
    results = {}
    for rt in RUNTIMES:
        srv, logs = _run_server(_mnist, runtime=rt, scheme_select="paper")
        results[rt] = (
            [l.selected for l in logs],
            np.asarray(obs.device_get(srv.state.residual)),
            np.asarray(obs.device_get(srv.state.history)))
    sel0, res0, hist0 = results["sequential"]
    for rt in RUNTIMES[1:]:
        sel, res, hist = results[rt]
        for a, b in zip(sel0, sel):
            np.testing.assert_array_equal(a, b, err_msg=rt)
        np.testing.assert_array_equal(res0, res, err_msg=rt)
        np.testing.assert_array_equal(hist0, hist, err_msg=rt)


@pytest.mark.parametrize("scheme", ALL_SCHEMES)
def test_server_drains_scheme_scalars(_mnist, scheme):
    mem = obs.OBS.configure(memory=True)
    srv, logs = _run_server(_mnist, scheme_select=scheme)
    assert len(logs) == 3
    rows = [e for e in mem.events if e.get("kind") == "round"]
    assert len(rows) == 3
    errs = SCHEMA.validate_events(mem.events, rounds=3, eval_every=1,
                                  scheme_select=scheme)
    assert errs == [], errs
    if scheme == "longterm_auction":
        assert isinstance(srv.state.scheme_state, SCH.LongTermState)
        spent = [r["budget_spent"] for r in rows]
        assert all(s >= 0.0 for s in spent)
