"""The profile reduction (bench/trace.py) and the per-layer readers on a
small recorded trace, written as an XSpace text proto: the plane and
line names are those a TPU v5e trace carries."""
import pytest
from jax.profiler import ProfileData

from bench import harness as H
from bench import trace as TR

MS = 1_000_000_000            # picoseconds in a millisecond


def _plane(pid, name, lines):
    names, body = {}, []
    for lid, (lname, events) in enumerate(lines, 1):
        evs = []
        for ename, start_ms, dur_ms in events:
            mid = names.setdefault(ename, len(names) + 1)
            evs.append(f"events {{ metadata_id: {mid} "
                       f"offset_ps: {int(start_ms * MS)} "
                       f"duration_ps: {int(dur_ms * MS)} }}")
        body.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 '
                    + " ".join(evs) + " }")
    meta = [f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for n, i in names.items()]
    return (f'planes {{ id: {pid} name: "{name}" ' + " ".join(body + meta)
            + " }")


MODULES = (("jit_train(7)", 0, 4), ("jit__eval(9)", 6, 2))


def recorded(devices=1, modules=MODULES):
    """A 10 ms traced window: ops busy 0-4 and 6-8 ms on every device,
    the host dispatching over 0-6 ms and flushing over 6-10 ms."""
    planes = [_plane(1, "/host:CPU", [("python", [
        ("bench/traced", 0, 10), ("bench/dispatch", 0, 6),
        ("bench/flush", 6, 4), ("unrelated", 0, 10)])])]
    for d in range(devices):
        planes.append(_plane(10 + d, f"/device:TPU:{d}", [
            ("XLA Ops", [("%fusion.1 = f32[4]{0} fusion(%p)", 0, 3),
                         ("convolution.2", 2, 2),
                         ("%fusion.1 = f32[4]{0} fusion(%p)", 6, 2)]),
            ("XLA Modules", list(modules))]))
    planes.append(_plane(99, "/device:TPU:0 SparseCore", []))
    return ProfileData.from_text_proto("\n".join(planes))


def test_reduce_busy_modules_and_gaps():
    s = TR.reduce_profile(recorded())
    assert s.window_s == pytest.approx(10e-3)
    assert s.busy_s == pytest.approx(6e-3)     # union of 0-4 and 6-8 ms
    assert s.devices == 1
    assert s.module_time(r"jit_train$") == pytest.approx(4e-3)
    assert s.module_time(r"jit__eval$") == pytest.approx(2e-3)
    assert s.op_s["fusion.1"] == pytest.approx(5e-3)
    # idle 4-6 ms falls in the dispatch, 8-10 ms in the flush; the
    # enclosing bench/traced and the unrelated host event label nothing
    assert s.idle_by_label() == pytest.approx(
        {"bench/dispatch": 2e-3, "bench/flush": 2e-3})
    b = s.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(5e-3)]
    assert len(b["idle_gaps"]) <= 10


def test_busy_is_averaged_over_devices():
    s = TR.reduce_profile(recorded(devices=4))
    assert s.devices == 4
    assert s.busy_s == pytest.approx(6e-3)
    assert s.module_time(r"jit_train$") == pytest.approx(16e-3)


def test_a_trace_without_the_window_is_refused():
    bad = ProfileData.from_text_proto(_plane(1, "/host:CPU", []))
    with pytest.raises(ValueError, match="bench/traced"):
        TR.reduce_profile(bad)


def _ctx(summary):
    return {"trace": summary, "rounds_traced": 2, "flops_traced": 1e9,
            "chips": 1, "peak_flops": 197e12, "host_assemble_s": 1e-3,
            "retraces_in_window": 0, "stage1_s": 1.5}


def test_readers_on_the_recorded_trace():
    spec = H.load_json(H.ROOT / "BENCHMARK.json")
    metrics = H.per_layer_metrics(spec, "paper100.c10")
    got = H.read_per_layer(metrics, _ctx(TR.reduce_profile(recorded())))
    assert got["device_idle_share.fl"]["value"] == pytest.approx(40.0)
    assert got["train_device_ms_per_round"]["value"] == pytest.approx(2.0)
    assert got["eval_device_ms_per_round"]["value"] == pytest.approx(1.0)
    assert got["mfu.fl"]["value"] == pytest.approx(
        100 * 1e9 / (10e-3 * 197e12))
    assert got["host_plan_ms_per_round"]["value"] == pytest.approx(0.5)
    assert got["retraces_in_window"]["value"] == 0
    assert got["stage1_s"]["value"] == 1.5


def test_a_reader_fails_loudly_on_a_missing_program(capsys):
    summary = TR.reduce_profile(recorded(modules=(("jit__eval(9)", 6, 2),)))
    reader = H.load_module(H.BENCH / "metrics"
                           / "train_device_ms_per_round.py")
    with pytest.raises(H.NothingToRead, match="jit_train"):
        reader.read(_ctx(summary))
    spec = H.load_json(H.ROOT / "BENCHMARK.json")
    got = H.read_per_layer(H.per_layer_metrics(spec, "paper100.c10"),
                           _ctx(summary))
    assert "train_device_ms_per_round" not in got
    assert "eval_device_ms_per_round" in got
    assert "train_device_ms_per_round" in capsys.readouterr().err
