"""The program-span labelling of idle gaps (bench/spans.py) on a small
recorded trace, and the readers of the program's ``stage3/`` and
``compile_s`` counters on made-up counters."""
import pytest
from jax.profiler import ProfileData

from bench import harness as H
from bench import spans as SP
from bench import trace as TR
from repro.obs import jax_stats

_plane = H.load_module(H.BENCH / "tests" / "test_trace.py")._plane


def recorded():
    """A 10 ms window: ops busy 0-1.5, 2.6-4 and 6-8 ms; the host
    dispatching over 0-6 ms and flushing over 6-10 ms, with the program's
    round spans inside the dispatch: select 0-2.5 ms > winner_fetch
    1.8-2.5 ms, then train 2.5-5.8 ms > assemble 2.5-4.4 ms, put
    4.4-5.6 ms."""
    host = [("bench/traced", 0, 10), ("bench/dispatch", 0, 6),
            ("bench/flush", 6, 4), ("unrelated", 0, 10),
            ("round/dispatch", 0, 5.8), ("round/select", 0, 2.5),
            ("round/winner_fetch", 1.8, 0.7), ("round/train", 2.5, 3.3),
            ("cohort/assemble", 2.5, 1.9), ("cohort/put", 4.4, 1.2)]
    planes = [_plane(1, "/host:CPU", [("python", host)]),
              _plane(10, "/device:TPU:0", [
                  ("XLA Ops", [("%fusion.1 = f32[4]{0} fusion(%p)", 0, 1.5),
                               ("convolution.2", 2.6, 1.4),
                               ("%fusion.1 = f32[4]{0} fusion(%p)", 6, 2)]),
                  ("XLA Modules", [("jit_train(7)", 0, 4)])])]
    return ProfileData.from_text_proto("\n".join(planes))


def test_program_spans_split_the_dispatch_gaps():
    data = recorded()
    gaps = SP.program_gaps(data)
    # the same gaps, under the same bench labels, as bench/trace.py gives
    old = TR.reduce_profile(data)
    assert old.idle_by_label() == pytest.approx(
        {"bench/dispatch": 3.1e-3, "bench/flush": 2e-3})
    for label, s in old.idle_by_label().items():
        assert sum(SP.split(gaps, label).values()) == pytest.approx(s)
    # each piece of a gap goes to the innermost span the host was in:
    # 1.5-2.6 ms and 4-6 ms
    assert SP.split(gaps, "bench/dispatch") == pytest.approx(
        {"round/select": 0.3e-3, "round/winner_fetch": 0.7e-3,
         "cohort/assemble": 0.5e-3, "cohort/put": 1.2e-3,
         "round/train": 0.2e-3, "host": 0.2e-3})


def test_a_gap_outside_every_program_span_is_host():
    gaps = SP.program_gaps(recorded())
    assert SP.split(gaps, "bench/flush") == pytest.approx({"host": 2e-3})


def _read(name, ctx=None):
    return H.load_module(H.BENCH / "metrics" / f"{name}.py").read(ctx or {})


def test_the_counter_readers(monkeypatch):
    monkeypatch.setattr(jax_stats, "flushed", {
        "stage3/assemblies": 4, "stage3/calls": 12,
        "stage3/serial_steps": 400, "stage3/step_slots": 1000,
        "stage3/steps_real": 640, "compile_s": 12.5})
    assert _read("train_serial_steps_per_round") == 100.0
    assert _read("train_step_fill") == pytest.approx(64.0)
    assert _read("setup_compile_s") == 12.5


@pytest.mark.parametrize("name", ["train_serial_steps_per_round",
                                  "train_step_fill", "setup_compile_s"])
def test_a_counter_reader_with_nothing_flushed(monkeypatch, name):
    monkeypatch.setattr(jax_stats, "flushed", {"traces": 3})
    with pytest.raises(H.NothingToRead):
        _read(name)
    monkeypatch.delattr(jax_stats, "flushed")     # a program without it
    with pytest.raises(H.NothingToRead):
        _read(name)
