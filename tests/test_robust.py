"""Byzantine-tolerant aggregation (repro.core.aggregation +
repro.sim.dynamics corruption model + the auction reputation loop):
attack semantics, screened-FedAvg estimator correctness, the
defense-off bit-equality boundary, cross-runtime quarantine
equivalence, strike-driven auction bans, and the device warm loop's
zero-retrace guarantee with defenses on."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import FLConfig
from repro.core import aggregation as AGG
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
# corruption model unit semantics
# ----------------------------------------------------------------------

def test_adversary_mask_deterministic_and_counted():
    cfg = _cfg(adversary_frac=0.3, attack="nan")
    m1 = np.asarray(DYN.adversary_mask(cfg))
    m2 = np.asarray(DYN.adversary_mask(cfg))
    np.testing.assert_array_equal(m1, m2)
    assert m1.sum() == round(0.3 * N_CLIENTS)
    assert not np.asarray(DYN.adversary_mask(_cfg())).any()
    # a different seed draws a different Byzantine set (whp for N=10, 3)
    m3 = np.asarray(DYN.adversary_mask(_cfg(adversary_frac=0.3,
                                            attack="nan", seed=4)))
    assert m3.sum() == m1.sum()


def _rows():
    rng = np.random.default_rng(0)
    deltas = jnp.asarray(rng.normal(size=(4, 6)).astype(np.float32))
    adv = jnp.array([True, False, True, False])
    valid = jnp.array([True, True, False, True])
    return deltas, adv, valid   # only row 0 is adv AND valid


@pytest.mark.parametrize("attack", ("nan", "scale", "signflip", "noise"))
def test_corrupt_updates_touches_only_valid_adversaries(attack):
    cfg = _cfg(adversary_frac=0.3, attack=attack, attack_scale=5.0)
    deltas, adv, valid = _rows()
    key = jax.random.PRNGKey(7)
    out = np.asarray(DYN.corrupt_updates(cfg, key, deltas, adv, valid))
    ref = np.asarray(deltas)
    # honest rows and the invalid adversarial row pass through bit-equal
    np.testing.assert_array_equal(out[1:], ref[1:])
    if attack == "nan":
        assert np.isnan(out[0]).all()
    elif attack == "scale":
        np.testing.assert_array_equal(out[0], 5.0 * ref[0])
    elif attack == "signflip":
        np.testing.assert_array_equal(out[0], -5.0 * ref[0])
    else:   # noise: perturbed, finite, and deterministic in the key
        assert np.isfinite(out[0]).all() and (out[0] != ref[0]).any()
        out2 = np.asarray(DYN.corrupt_updates(cfg, key, deltas, adv,
                                              valid))
        np.testing.assert_array_equal(out, out2)


def test_corrupt_updates_identity_when_inactive():
    deltas, adv, valid = _rows()
    key = jax.random.PRNGKey(7)
    for cfg in (_cfg(), _cfg(attack="scale"),            # frac 0
                _cfg(adversary_frac=0.3)):               # attack none
        out = DYN.corrupt_updates(cfg, key, deltas, adv, valid)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(deltas))


# ----------------------------------------------------------------------
# screened-step estimator semantics
# ----------------------------------------------------------------------

def _screen_inputs(cfg, deltas, weights, valid, adv=None, round_idx=0):
    cap = deltas.shape[0]
    adv = np.zeros(cap, bool) if adv is None else np.asarray(adv)
    ids = np.where(np.asarray(valid), np.arange(cap), -1).astype(np.int32)
    strikes = jnp.zeros((cfg.num_clients,), jnp.float32)
    key = jax.random.PRNGKey(0)
    return (jnp.asarray(deltas, jnp.float32),
            jnp.asarray(weights, jnp.float32), jnp.asarray(valid),
            jnp.asarray(adv), jnp.asarray(ids), strikes,
            AGG.init_defense_state(cfg), jnp.int32(round_idx), key)


def test_screen_none_is_plain_weighted_sum():
    cfg = _cfg(defense="none")
    screen = AGG.make_screened_step(cfg)
    rng = np.random.default_rng(1)
    deltas = rng.normal(size=(4, 8)).astype(np.float32)
    w = np.array([0.3, 0.3, 0.4, 0.0], np.float32)
    valid = np.array([True, True, True, False])
    agg, strikes, _, rep = screen(*_screen_inputs(cfg, deltas, w, valid))
    np.testing.assert_allclose(np.asarray(agg), (w * valid) @ deltas,
                               rtol=1e-6, atol=1e-7)
    assert int(rep["num_quarantined"]) == 0
    assert not np.asarray(strikes).any()


def test_screen_none_propagates_nan():
    # the attack baseline must NOT be silently rescued by quarantine
    cfg = _cfg(defense="none")
    screen = AGG.make_screened_step(cfg)
    deltas = np.ones((4, 8), np.float32)
    deltas[1] = np.nan
    w = np.full(4, 0.25, np.float32)
    agg, strikes, _, rep = screen(
        *_screen_inputs(cfg, deltas, w, np.ones(4, bool)))
    assert np.isnan(np.asarray(agg)).all()
    assert int(rep["num_quarantined"]) == 0
    assert not np.asarray(strikes).any()
    # metrics stay finite: computed over finite rows only
    assert np.isfinite(float(rep["update_norm_p50"]))


def test_quarantine_excludes_and_renormalizes():
    cfg = _cfg(defense="clip", clip_mult=1e9)   # clip never binds here
    screen = AGG.make_screened_step(cfg)
    rng = np.random.default_rng(2)
    deltas = rng.normal(size=(4, 8)).astype(np.float32)
    deltas[2] = np.inf
    w = np.array([0.2, 0.3, 0.4, 0.1], np.float32)
    valid = np.ones(4, bool)
    agg, strikes, _, rep = screen(*_screen_inputs(cfg, deltas, w, valid))
    keep = np.array([0, 1, 3])
    expect = (w[keep] / w[keep].sum()) @ deltas[keep]
    np.testing.assert_allclose(np.asarray(agg), expect, rtol=1e-5,
                               atol=1e-6)
    assert int(rep["num_quarantined"]) == 1
    assert int(rep["num_survivors"]) == 3
    # one strike scattered to the quarantined client's global id (=2)
    s = np.asarray(strikes)
    assert s[2] == 1.0 and s.sum() == 1.0


@pytest.mark.parametrize("defense", ("trimmed", "median"))
def test_trimmed_and_median_resist_outlier(defense):
    cfg = _cfg(defense=defense)
    screen = AGG.make_screened_step(cfg)
    deltas = np.ones((8, 4), np.float32)
    deltas[0] = 1e6                              # one huge-but-finite row
    w = np.full(8, 1 / 6, np.float32)
    w[6:] = 0.0
    valid = np.zeros(8, bool)
    valid[:6] = True
    agg, _, _, rep = screen(*_screen_inputs(cfg, deltas, w, valid))
    a = np.asarray(agg)
    np.testing.assert_allclose(a, 1.0, rtol=1e-5)   # outlier trimmed out
    assert int(rep["num_quarantined"]) == 0
    # defense=none would have been dragged by the outlier
    cfg0 = _cfg(defense="none")
    agg0, _, _, _ = AGG.make_screened_step(cfg0)(
        *_screen_inputs(cfg0, deltas, w, valid))
    assert np.asarray(agg0).max() > 1e4


def test_clip_defense_bounds_outlier_norm():
    cfg = _cfg(defense="clip")                   # clip_mult default
    screen = AGG.make_screened_step(cfg)
    rng = np.random.default_rng(3)
    deltas = rng.normal(size=(8, 16)).astype(np.float32)
    deltas[0] *= 1e4
    w = np.full(8, 0.125, np.float32)
    valid = np.ones(8, bool)
    agg, _, dstate, rep = screen(
        *_screen_inputs(cfg, deltas, w, valid))
    honest_max = np.abs(deltas[1:]).max()
    assert np.abs(np.asarray(agg)).max() < 10 * honest_max
    assert float(rep["clipped_frac"]) > 0
    assert float(dstate.clip_ema) > 0            # running median seeded
    assert float(rep["update_norm_p99"]) >= float(rep["update_norm_p50"])


def test_screen_zero_survivors_yields_zero_delta():
    cfg = _cfg(defense="median")
    screen = AGG.make_screened_step(cfg)
    deltas = np.full((4, 8), np.nan, np.float32)
    w = np.full(4, 0.25, np.float32)
    agg, strikes, _, rep = screen(
        *_screen_inputs(cfg, deltas, w, np.ones(4, bool)))
    np.testing.assert_array_equal(np.asarray(agg), 0.0)
    assert int(rep["num_quarantined"]) == 4
    assert int(rep["num_survivors"]) == 0
    assert np.asarray(strikes).sum() == 4.0


def test_screen_capacity_is_pow2_bound():
    cfg = _cfg()
    cap = AGG.screen_capacity(cfg)
    assert cap & (cap - 1) == 0
    assert cap >= round(cfg.select_ratio * cfg.num_clients)


# ----------------------------------------------------------------------
# bit-equality boundary: neutral knobs change NOTHING
# ----------------------------------------------------------------------

@pytest.mark.parametrize("runtime,defense_mode", [
    pytest.param(rt, mode, id=rt if mode == "static" else f"{rt}-{mode}")
    for mode in ("static", "adaptive") for rt in RUNTIMES])
def test_defense_knobs_off_bit_identical(runtime, defense_mode, data):
    plain = _server(_cfg(runtime=runtime, rounds=2), data)
    logs_p = plain.run(rounds=2)
    # knobs present but neutral: frac 0 + defense none => defended False
    knobs = _server(_cfg(runtime=runtime, rounds=2, adversary_frac=0.0,
                         attack="scale", attack_scale=9.0,
                         defense="none", defense_mode=defense_mode), data)
    assert not knobs.defended
    logs_k = knobs.run(rounds=2)
    _assert_trees_equal(plain.params, knobs.params)
    for lp, lk in zip(logs_p, logs_k):
        np.testing.assert_array_equal(lp.selected, lk.selected)
        assert lp.mean_bid == lk.mean_bid
    assert knobs.state.strikes is None   # feature-off pytree unchanged


# ----------------------------------------------------------------------
# cross-runtime quarantine / reputation equivalence
# ----------------------------------------------------------------------

def test_nan_attack_quarantine_equivalent_across_runtimes(data):
    outs = {}
    for rt in RUNTIMES:
        srv = _server(_cfg(runtime=rt, rounds=3, adversary_frac=0.3,
                           attack="nan", defense="median"), data)
        logs = srv.run(rounds=3)
        for lf in _leaves(srv.params):
            assert np.isfinite(lf).all()   # median survives NaN rows
        outs[rt] = (np.asarray(obs.device_get(srv.state.strikes)),
                    [np.asarray(l.selected) for l in logs],
                    srv.defense_totals["quarantined"])
    ref_s, ref_sel, ref_q = outs["sequential"]
    assert ref_q > 0                       # the attack actually landed
    for rt in RUNTIMES[1:]:
        s, sel, q = outs[rt]
        # quarantine verdicts (non-finiteness) are reassociation-immune,
        # so strikes, selections and totals match bit-for-bit
        np.testing.assert_array_equal(s, ref_s)
        assert q == ref_q
        for a, b in zip(sel, ref_sel):
            np.testing.assert_array_equal(a, b)


def test_strikes_ban_repeat_offenders(data):
    cfg = _cfg(rounds=6, adversary_frac=0.3, attack="nan",
               defense="median", strike_threshold=1.0, strike_decay=1.0)
    srv = _server(cfg, data)
    adv = np.asarray(obs.device_get(DYN.adversary_mask(cfg)), bool)
    logs = srv.run(rounds=6)
    strikes = np.asarray(obs.device_get(srv.state.strikes))
    assert (strikes[~adv] == 0).all()      # honest clients never struck
    banned_at = {}                         # client -> first banned round
    struck = set()
    for log in logs:
        for c in log.selected:
            assert int(c) not in banned_at, \
                f"client {c} selected after ban (round {log.round})"
        # strikes land AFTER this round's selection: a client struck in
        # round t is banned from round t+1 on (threshold 1, no decay)
        for c in log.selected:
            if adv[int(c)]:
                struck.add(int(c))
                banned_at.setdefault(int(c), log.round + 1)
    assert struck                          # some adversary won at least once
    assert srv.defense_totals["banned_final"] == len(struck)
    assert (strikes[list(struck)] >= cfg.strike_threshold).all()


# ----------------------------------------------------------------------
# eval_skipped flag + divergence accounting (satellite S2)
# ----------------------------------------------------------------------

def test_eval_skipped_flag_tracks_cadence(data, tmp_path):
    path = str(tmp_path / "ev.jsonl")
    obs.OBS.configure(jsonl=path, memory=True)
    srv = _server(_cfg(rounds=4, eval_every=2), data)
    logs = srv.run(rounds=4)
    obs.OBS.flush()
    for log in logs:
        due = log.round % 2 == 0 or log.round == 3
        assert log.eval_skipped == (not due)
        assert np.isnan(log.test_acc) == log.eval_skipped
    assert validate_events(load_jsonl(path), rounds=4, eval_every=2) == []


def test_undefended_nan_attack_flags_divergence(data, tmp_path):
    path = str(tmp_path / "ev.jsonl")
    obs.OBS.configure(jsonl=path, memory=True)
    srv = _server(_cfg(rounds=3, eval_every=1, adversary_frac=0.3,
                       attack="nan", defense="none"), data)
    logs = srv.run(rounds=3)
    obs.OBS.flush()
    diverged = [l for l in logs
                if not l.eval_skipped and not np.isfinite(l.test_loss)]
    assert diverged                        # NaN reached the globals
    events = load_jsonl(path)
    assert any(e.get("kind") == "defense"
               and e.get("name") == "round/diverged" for e in events)
    # NaN acc with eval_skipped=false is legal under the new schema
    assert validate_events(events, rounds=3, eval_every=1) == []


# ----------------------------------------------------------------------
# compile-once policy: defended warm loop never retraces
# ----------------------------------------------------------------------

def test_device_defended_warm_loop_zero_retrace(data):
    cfg = _cfg(runtime="device", rounds=8, adversary_frac=0.3,
               attack="scale", defense="trimmed")
    srv = _server(cfg, data)
    base = obs.jax_stats.snapshot()        # process-wide counters: other
    srv.run(rounds=3)                      # tests may have compiled too
    snap = obs.jax_stats.snapshot()
    assert obs.jax_stats.delta(base).get("traces/screened_agg") == 1
    for t in range(3, 8):                  # shifting cohorts, warm
        srv._dispatch_round(t, eval_now=False)
    srv._flush_pending()
    d = obs.jax_stats.delta(snap)
    retraces = {k: v for k, v in d.items() if k.startswith("traces")}
    assert not retraces, retraces
