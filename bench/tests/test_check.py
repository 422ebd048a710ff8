"""The correctness check of the ``fl_rounds`` driver at a size a CPU test
run holds: the program's rounds pass it; the control (the reference in
bfloat16, put in the program's place) fails it; and a run whose timed
path is broken underneath fails it, once for each fault a one-chip FL
cell can have.  These drive the rest of a run with the harness's look
for a chip left out."""
import json
import time

import numpy as np
import pytest

from bench import harness as H
from bench.reference import fl as REF
from repro.core import server as SRV
from repro.sim import runtime as RT

DRIVER = H.load_module(H.BENCH / "drivers" / "fl_rounds.py")


def small_cell(seed=2**33 + 7, seconds=1.0):
    config = H.load_json(H.BENCH / "configs" / "cnn_mnist.paper100.json")
    config["data"].update(pool=1200, test_pool=300, test_batch=100)
    config["fl"].update(num_clients=12, num_clusters=3)
    # a CPU's default matmul precision keeps float32 operands
    config["matmul_operands"] = "float32"
    traffic = H.load_json(H.BENCH / "traffic" / "paper100.c10.json")
    traffic.update(rounds_per_job=4, warm_rounds=2, check_rounds=4)
    return H.Cell(name="paper100.c10", config=config, traffic=traffic,
                  seed=seed, seconds=seconds, trace=False,
                  t_start=time.perf_counter(), peak_flops=1e12)


def correct(checks):
    return all(v <= lim for v, lim in checks.values())


@pytest.fixture(scope="module")
def measured():
    cell = small_cell()
    res, fleet, cases = DRIVER.measure(cell)
    return cell, res, fleet, cases


def test_the_program_passes(measured):
    cell, res, fleet, cases = measured
    assert res.attempted >= 4 and res.failed == 0
    assert len(cases) >= 4
    checks = DRIVER.check(fleet, cases, cell.traffic["limits"])
    assert set(checks) == set(cell.traffic["limits"])
    assert correct(checks), checks


def test_the_control_fails(measured):
    cell, _, fleet, cases = measured
    control = [(inp, REF.answer(fleet, inp, got.eval_loss is not None,
                                REF.BF16)) for inp, got in cases]
    checks = DRIVER.check(fleet, control, cell.traffic["limits"])
    assert not correct(checks), checks


def _state_unchanged(monkeypatch):
    """A round that returns its weights unchanged."""
    monkeypatch.setattr(RT.DeviceRuntime, "train_cohort",
                        lambda self, params, sel_idx, history: params)


def _half_the_cohort(monkeypatch):
    """Half of the winners left out, FedAvg taken over the rest."""
    train = RT.DeviceRuntime.train_cohort

    def half(self, params, sel_idx, history):
        sel_idx = np.asarray(sel_idx)
        return train(self, params, sel_idx[:max(sel_idx.size // 2, 1)],
                     history)

    monkeypatch.setattr(RT.DeviceRuntime, "train_cohort", half)


def _answer_altered(monkeypatch):
    """A winner of each round replaced where the winners are produced:
    the fetched winner mask names another client."""
    get = SRV.obs.device_get

    def altered(tree):
        out = get(tree)
        if isinstance(out, np.ndarray) and out.dtype == bool:
            out = np.roll(out, 1)
        return out

    monkeypatch.setattr(SRV.obs, "device_get", altered)


def _one_leaf_frozen(monkeypatch):
    """The smallest leaf, the last layer's bias, left as the round found
    it while the others move."""
    train = RT.DeviceRuntime.train_cohort

    def frozen(self, params, sel_idx, history):
        out = train(self, params, sel_idx, history)
        return out if out is None else dict(out, f2_b=params["f2_b"])

    monkeypatch.setattr(RT.DeviceRuntime, "train_cohort", frozen)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_the_cohort,
                                   _answer_altered, _one_leaf_frozen])
def test_a_broken_timed_path_fails(monkeypatch, fault):
    fault(monkeypatch)
    spec = H.load_json(H.ROOT / "BENCHMARK.json")
    line = json.loads(H.execute(spec, small_cell(seed=31),
                                {"platform": "cpu", "kind": "cpu",
                                 "count": 1}))
    assert line["attempted"] > 0
    assert line["correct"] is False, line["checks"]
    assert list(line)[-1] == "checks"


def test_a_tie_in_the_probe_admits_either_threshold():
    """Bids of one probe cluster as the chip read them: the lowest two
    lie 1.4e-6 apart, and float32 may order them either way."""
    sizes = np.array([233, 929, 824, 392])
    bid = np.array([0.6880298312, 0.6880308160, 0.6894150497, 0.6962148874])
    members = np.arange(4)
    assert REF.size_floors(sizes, bid, members, 1) == [233, 929]
    apart = bid + np.array([0.0, 1e-3, 0.0, 0.0])
    assert REF.size_floors(sizes, apart, members, 1) == [233]
    assert REF.size_floors(sizes, bid, members, 2) == [233]
    assert REF.size_floors(sizes, bid, members[:0], 1) == [0]
