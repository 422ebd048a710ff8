"""Observability layer (repro.obs): registry/sink/span mechanics, the
event schema, and — most importantly — the neutrality guarantees:
instrumentation must not add retraces, blocking fetches, or implicit
host transfers to the round pipeline, and the logs it observes must be
bit-identical to an uninstrumented run's.  Also regression-tests the
verbose-print eval bug (progress printing used to force off-cadence
evals, so logs and params depended on the ``verbose`` flag)."""
import io
import json
import math
from contextlib import redirect_stdout

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs.base import FLConfig
from repro.core.adapters import cnn_adapter
from repro.core.server import FederatedServer
from repro.data.partition import partition_clients
from repro.data.synthetic import make_image_dataset
from repro.obs.schema import load_jsonl, validate_events
from repro.obs.sinks import sanitize_event

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


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with obs disabled and counters zeroed
    (OBS is a process singleton)."""
    obs.OBS.reset()
    yield
    obs.OBS.reset()


def _server(data, clients, **cfg_kw):
    train, test = data
    cfg = _cfg(**cfg_kw)
    return FederatedServer(cfg, cnn_adapter("mnist"), train.x, train.y,
                           clients, {"x": test.x[:64], "y": test.y[:64]})


def _canon(v):
    # NaN != NaN would make off-cadence rounds incomparable
    return "nan" if isinstance(v, float) and math.isnan(v) else v


def _log_tuples(logs):
    return [tuple(map(_canon, (l.round, l.test_acc, l.test_loss,
                               l.energy_std, l.mean_bid, l.server_reward,
                               l.client_reward_sum, l.vds_gap)))
            + (tuple(l.selected.tolist()),) for l in logs]


# ----------------------------------------------------------------------
# registry / span / sink mechanics
# ----------------------------------------------------------------------

def test_disabled_is_noop():
    assert not obs.OBS.enabled
    # the hot-path entry points must not buffer anything while disabled
    s = obs.span("x")
    assert s is obs.span("y"), "disabled span must be the shared null cm"
    with s:
        pass
    obs.OBS.event("round", round=0)
    obs.OBS.record_round(1, test_acc=1.0)
    assert obs.OBS._buffer == []


def test_span_nesting_and_schema():
    mem = obs.configure(memory=True)
    with obs.span("run/cluster"):
        with obs.span("cluster/kmeans", k=3):
            pass
    with obs.span("round/dispatch", round=0):
        with obs.span("round/select", round=0):
            pass
    obs.OBS.record_round(0, test_acc=0.5, test_loss=1.0, energy_std=0.1,
                         mean_bid=0.2, vds_gap=0.3)
    with obs.span("round/drain", rounds=1):
        pass
    obs.flush()
    errs = validate_events(mem.events, rounds=1, eval_every=1)
    assert errs == [], errs
    spans = {e["name"]: e for e in mem.events if e["kind"] == "span"}
    assert spans["cluster/kmeans"]["parent"] == spans["run/cluster"]["id"]
    assert spans["cluster/kmeans"]["depth"] == 1
    assert spans["round/select"]["parent"] == spans["round/dispatch"]["id"]
    assert spans["run/cluster"]["parent"] is None
    # meta keys clashing with schema fields are renamed, not dropped
    with obs.span("x", kind="boom", note="ok"):
        pass
    obs.flush()
    e = [v for v in mem.events if v.get("name") == "x"][0]
    assert e["kind"] == "span" and e["meta_kind"] == "boom" \
        and e["note"] == "ok"


def test_sinks_sanitize_nan_and_jsonl_roundtrip(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    obs.configure(jsonl=path)
    obs.OBS.record_round(0, test_acc=float("nan"), test_loss=float("inf"),
                         energy_std=0.5, mean_bid=0.1, vds_gap=0.2)
    obs.OBS.counter("pack/buckets", 3)
    obs.flush()
    events = load_jsonl(path)       # strict JSON: NaN would raise here
    row = [e for e in events if e["kind"] == "round"][0]
    assert row["test_acc"] is None and row["test_loss"] is None
    assert row["energy_std"] == 0.5
    ctr = [e for e in events if e["kind"] == "counter"][0]
    assert ctr["name"] == "pack/buckets" and ctr["value"] == 3
    assert sanitize_event({"a": math.nan, "b": 1.5}) == {"a": None,
                                                         "b": 1.5}


def test_jax_stats_counters_and_transfer_accounting():
    st0 = obs.jax_stats.snapshot()
    arr = np.ones((8, 4), np.float32)
    dev = obs.device_put(arr)
    back = obs.device_get(dev)
    d = obs.jax_stats.delta(st0)
    assert d["h2d_bytes"] == arr.nbytes and d["h2d_calls"] == 1
    assert d["d2h_bytes"] == back.nbytes and d["d2h_calls"] == 1

    @jax.jit
    def f(x):
        obs.jax_stats.note_trace("t_test")
        return x * 2

    st1 = obs.jax_stats.snapshot()
    f(dev)
    f(dev)    # cache hit: no second trace
    d = obs.jax_stats.delta(st1)
    assert d.get("traces/t_test") == 1


def test_compile_seconds_move_on_a_fresh_jit_only():
    f = jax.jit(lambda x: x * 3 + 1)
    x = obs.device_put(np.ones((5,), np.float32))
    st = obs.jax_stats.snapshot()
    f(x).block_until_ready()
    d = obs.jax_stats.delta(st)
    assert d["compile_s"] > 0
    phases = d["compile/trace_s"] + d["compile/lower_s"] \
        + d["compile/backend_s"]
    assert d["compile_s"] <= phases + 1e-9     # the union, not the sum
    st = obs.jax_stats.snapshot()
    f(x).block_until_ready()                   # cached: nothing compiles
    assert not any(k.startswith("compile")
                   for k in obs.jax_stats.delta(st))


def _host_spans(trace_dir):
    """The host-plane events of the trace under ``trace_dir`` named as
    the program's round spans: name -> (start_ns, end_ns)."""
    from jax.profiler import ProfileData
    path = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("round/"):
                    out[e.name] = (e.start_ns, e.end_ns)
    return out


@pytest.mark.parametrize("mode", ["sink", "off", "maybe_profile"])
def test_spans_reach_the_profiler_trace(mode, tmp_path):
    """While recording, each span is a profiler annotation of its own
    name (nested as the spans nest); with no sink and no maybe_profile,
    span() stays the shared null object and the trace holds none."""
    if mode == "sink":
        obs.configure(memory=True)
    capture = (obs.maybe_profile(tmp_path) if mode == "maybe_profile"
               else jax.profiler.trace(str(tmp_path)))
    with capture:
        if mode == "off":
            assert obs.span("a") is obs.span("b")
        with obs.span("round/dispatch", round=0):
            with obs.span("round/select", round=0):
                with obs.span("round/winner_fetch", round=0):
                    obs.device_get(obs.device_put(np.ones(3)))
    assert obs.OBS.recording == (mode == "sink")   # the capture ended
    spans = _host_spans(tmp_path)
    if mode == "off":
        assert spans == {}
        return
    (d0, d1), (s0, s1), (w0, w1) = (
        spans[n] for n in ("round/dispatch", "round/select",
                           "round/winner_fetch"))
    assert d0 <= s0 <= w0 < w1 <= s1 <= d1


def test_sync_audit_flags_implicit_transfers():
    f = jax.jit(lambda x: x + 1)
    host = np.ones((4,), np.float32)
    f(host)   # compile outside the guard
    with pytest.raises(Exception, match="[Dd]isallow"):
        with obs.sync_audit():
            jax.block_until_ready(f(host))   # implicit h2d
    dev = obs.device_put(host)
    with obs.sync_audit():                   # explicit transfers are legal
        out = f(dev)
        obs.device_get(out)


# ----------------------------------------------------------------------
# satellite 1: verbose printing must not change eval cadence
# ----------------------------------------------------------------------

def test_verbose_does_not_force_evals(data, clients):
    rounds, eval_every = 5, 3
    srv_q = _server(data, clients, eval_every=eval_every)
    logs_q = srv_q.run(rounds=rounds, verbose=False)
    srv_v = _server(data, clients, eval_every=eval_every)
    with redirect_stdout(io.StringIO()) as cap:
        logs_v = srv_v.run(rounds=rounds, verbose=True)
    # logs AND params bit-identical with verbose on/off (the old code
    # forced an eval at every print boundary, so they weren't)
    assert _log_tuples(logs_q) == _log_tuples(logs_v)
    for a, b in zip(jax.tree.leaves(jax.device_get(srv_q.params)),
                    jax.tree.leaves(jax.device_get(srv_v.params))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # eval cadence: due exactly on multiples of eval_every + final round
    for l in logs_v:
        due = l.round % eval_every == 0 or l.round == rounds - 1
        assert math.isnan(l.test_acc) != due
    # the round-0 progress line shows round 0's drained eval
    assert "round   0 acc=0." in cap.getvalue()


# ----------------------------------------------------------------------
# tentpole: instrumentation neutrality on the device round pipeline
# ----------------------------------------------------------------------

def test_observability_is_neutral_on_device_runtime(data, clients,
                                                    tmp_path):
    _check_neutral(data, clients, tmp_path, profile=False)


def test_observability_is_neutral_under_the_profiler(data, clients,
                                                     tmp_path):
    _check_neutral(data, clients, tmp_path, profile=True)


def _check_neutral(data, clients, tmp_path, profile):
    rounds = 4
    # uninstrumented twin first (obs disabled via the autouse fixture)
    srv0 = _server(data, clients, runtime="device", eval_every=2)
    logs0 = srv0.run(rounds=rounds)
    params0 = jax.device_get(srv0.params)

    path = str(tmp_path / "ev.jsonl")
    mem = obs.configure(jsonl=path, memory=True)
    srv1 = _server(data, clients, runtime="device", eval_every=2)
    # warm-up: clustering + class compiles + rounds 0-1 (same eval
    # cadence as run(rounds=4) — round 1 is NOT final here)
    srv1.cluster()
    srv1.runtime.warmup(srv1.params)
    for t in range(2):
        srv1._dispatch_round(t, srv1._eval_due(t, final=False))
    srv1._flush_pending()
    st = obs.jax_stats.snapshot()
    with obs.maybe_profile(tmp_path / "trace" if profile else None), \
            obs.sync_audit():               # no implicit host transfers
        for t in range(2, rounds):
            srv1._dispatch_round(t, srv1._eval_due(t, final=t == rounds - 1))
    srv1._flush_pending()
    d = obs.jax_stats.delta(st)
    assert not any(k.startswith("traces") for k in d), \
        f"instrumented warm rounds retraced: {d}"

    # selection/energy logs bit-identical to the uninstrumented twin
    assert _log_tuples(logs0) == _log_tuples(srv1.logs)
    params1 = jax.device_get(srv1.params)
    for a, b in zip(jax.tree.leaves(params0), jax.tree.leaves(params1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    obs.flush()
    errs = validate_events(mem.events, rounds=rounds, eval_every=2)
    assert errs == [], errs
    # the JSONL mirror carries the same stream
    assert validate_events(load_jsonl(path), rounds=rounds,
                           eval_every=2) == []
    # dispatch and drain are recorded separately
    names = [e["name"] for e in mem.events if e["kind"] == "span"]
    assert names.count("round/dispatch") == rounds
    assert names.count("round/winner_fetch") == rounds
    assert "cohort/put" in names
    assert "round/drain" in names
    if profile:
        assert "round/winner_fetch" in _host_spans(tmp_path / "trace")


def test_schema_validator_catches_violations():
    base = {"kind": "span", "ts": 1.0, "name": "a", "id": 1,
            "parent": None, "depth": 0, "t0": 0.0, "dur_s": 1.0}
    # child escaping its parent's window
    bad_child = {"kind": "span", "ts": 3.0, "name": "b", "id": 2,
                 "parent": 1, "depth": 1, "t0": 0.5, "dur_s": 5.0}
    errs = validate_events([base, bad_child])
    assert any("escapes" in e for e in errs)
    # wrong depth
    bad_depth = dict(bad_child, t0=0.1, dur_s=0.1, depth=4)
    assert any("depth" in e for e in validate_events([base, bad_depth]))
    # duplicate round rows + off-cadence eval number
    r = {"kind": "round", "ts": 1.0, "round": 1, "test_acc": 0.5,
         "test_loss": 1.0, "energy_std": 0.1, "mean_bid": 0.2,
         "vds_gap": 0.3}
    r0 = dict(r, round=0, test_acc=None, test_loss=None)
    disp = [dict(base, id=10 + t, name="round/dispatch", round=t)
            for t in range(2)]
    drain = dict(base, id=20, name="round/drain")
    errs = validate_events([r0, r, *disp, drain], rounds=2, eval_every=2)
    assert any("eval due but" in e for e in errs)       # round 0 null
    errs = validate_events([r, dict(r), *disp, drain], rounds=2,
                           eval_every=2)
    assert any("duplicate series row" in e for e in errs)
