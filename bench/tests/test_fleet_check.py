"""The ``fl_fleet`` driver at a size a CPU test run holds, on the fleet
cell's own configuration and traffic shrunk to 32 clients whose shards
run from 8 to 80 samples (so some are smaller than a batch): one traced
run, whose rounds the program passes and the control, half a cohort and
a frozen leaf fail; a masked batch that divides by its slots, or counts
its padded slots, moves the update far past the program's own reading;
its checked rounds hold ``check_evals`` eval rounds; its traced job runs
``trace_rounds`` rounds; every per-layer metric of the cell finds
something to read; and a fleet store with more class programs than a
cold set-up compiles in time stops the run before stage 1."""
import dataclasses
import time

import numpy as np
import pytest

from bench import harness as H
from bench import trace as TR
from bench.reference import cnn_mnist as M
from bench.reference import fl as REF

DRIVER = H.load_module(H.BENCH / "drivers" / "fl_fleet.py")
CALIBRATE = H.load_module(H.BENCH / "calibrate.py")
CELL = "emnist3383.c330"


def small_cell(out_dir, seed=2**33 + 11, seconds=1.0):
    spec = H.load_json(H.ROOT / "BENCHMARK.json")
    w = H.workload(spec, CELL)
    config = H.load_json(H.BENCH / "configs" / f"{w['config']}.json")
    config["data"].update(pool=1600, test_pool=300, test_batch=100)
    config["fl"].update(num_clients=32, num_clusters=4, select_ratio=0.125)
    # a CPU's default matmul precision keeps float32 operands
    config["matmul_operands"] = "float32"
    traffic = H.load_json(H.BENCH / "traffic" / f"{w['traffic']}.json")
    traffic.update(rounds_per_job=6, eval_every=3, warm_rounds=2,
                   trace_rounds=3, check_rounds=4, check_evals=2)
    return H.Cell(name=CELL, config=config, traffic=traffic, seed=seed,
                  seconds=seconds, trace=True, t_start=time.perf_counter(),
                  peak_flops=1e12, out_dir=out_dir)


def correct(checks):
    return all(v <= lim for v, lim in checks.values())


@pytest.fixture(scope="module")
def measured(tmp_path_factory):
    """One traced run; the CPU's profile has no TPU plane, so the reduced
    trace is a stand-in."""
    cell = small_cell(tmp_path_factory.mktemp("bench_out"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(TR, "reduce_file", lambda path: TR.Summary(
            window_s=1.0, busy_s=0.5, devices=1,
            module_s={"jit_train": 0.25, "jit__round_step_jit": 0.01,
                      "jit__eval": 0.02}, op_s={}))
        res, fleet, cases = DRIVER.measure(cell)
    return cell, res, fleet, cases


def test_the_fleet_has_shards_smaller_than_a_batch(measured):
    _, _, fleet, _ = measured
    assert fleet.sizes.min() < 32 < fleet.sizes.max()


def test_the_program_passes(measured):
    cell, res, fleet, cases = measured
    assert res.attempted >= 4 and res.failed == 0
    assert len(cases) == cell.traffic["check_rounds"]
    checks = DRIVER.check(fleet, cases, cell.traffic["limits"])
    assert set(checks) == set(cell.traffic["limits"])
    assert correct(checks), checks


def test_the_checked_rounds_hold_the_eval_rounds(measured):
    cell, _, _, cases = measured
    evals = sum(got.eval_loss is not None for _, got in cases)
    assert evals >= cell.traffic["check_evals"]


def test_the_traced_job_runs_trace_rounds(measured):
    cell, res, _, _ = measured
    assert res.ctx["rounds_traced"] == cell.traffic["trace_rounds"]
    assert res.ctx["retraces_in_window"] == 0


def test_every_per_layer_metric_of_the_cell_reads(measured):
    _, res, _, _ = measured
    spec = H.load_json(H.ROOT / "BENCHMARK.json")
    metrics = H.per_layer_metrics(spec, CELL)
    got = H.read_per_layer(metrics, res.ctx)
    assert set(got) == {m["name"] for m in metrics}
    assert 0 < got["train_sample_fill.fleet"]["value"] < 100
    assert got["class_calls_per_round.fleet"]["value"] >= 1
    assert got["retraces_in_window.fleet"]["value"] == 0


def test_the_control_fails(measured):
    cell, _, fleet, cases = measured
    control = [(inp, REF.answer(fleet, inp, got.eval_loss is not None,
                                REF.BF16)) for inp, got in cases]
    checks = DRIVER.check(fleet, control, cell.traffic["limits"])
    assert not correct(checks), checks


def _half_the_cohort(fleet, inp, got):
    return CALIBRATE.half_cohort(REF, fleet, inp, got)


def _one_leaf_frozen(fleet, inp, got):
    """The last layer's bias left as the round found it."""
    return dataclasses.replace(got, params=dict(
        got.params, f2_b=inp.params["f2_b"]))


@pytest.mark.parametrize("fault", [_half_the_cohort, _one_leaf_frozen])
def test_a_faulty_round_fails(measured, fault):
    cell, _, fleet, cases = measured
    gap = max(REF.compare(fleet, inp, fault(fleet, inp, got))["update_gap"]
              for inp, got in cases)
    assert gap > cell.traffic["limits"]["update_gap"]


def masked_batch_fault(fleet, inp, got, kind):
    """``got`` with its weights replaced by the reference's FedAvg over
    its winners, where each winner whose shard is smaller than a batch
    runs its one step as a faulty masked batch of 32 slots would:
    ``"per_slot"`` divides its n samples' summed loss by 32, and
    ``"pads_count"`` also counts the 32 - n padded slots, each a copy of
    the shard's first sample (the program's padding index)."""
    exact = REF.local_train

    def local_train(fleet, params, client, participations, dt):
        shard = fleet.train_idx[client]
        n = len(shard)
        if n >= 32:
            return exact(fleet, params, client, participations, dt)
        xl, yl = fleet.x[shard], fleet.y[shard]
        rng = np.random.default_rng(int(participations) * 977 + int(client))
        lr = fleet.c["lr"]
        p = params
        for _ in range(fleet.c["local_epochs"]):
            idx = rng.permutation(n)
            if kind == "per_slot":
                p = M.sgd_step(p, xl[idx], yl[idx], lr * n / 32, dt,
                               fleet.operands)
            else:
                idx = np.concatenate([idx, np.zeros(32 - n, idx.dtype)])
                p = M.sgd_step(p, xl[idx], yl[idx], lr, dt, fleet.operands)
        return p

    REF.local_train = local_train
    try:
        params = REF.on_cpu(REF.fedavg)(fleet, inp.params, got.win,
                                        inp.history, np.float32)
    finally:
        REF.local_train = exact
    return dataclasses.replace(got, params=params, eval_loss=None)


@pytest.mark.parametrize("kind", ["per_slot", "pads_count"])
def test_a_masked_batch_fault_moves_the_update(measured, kind):
    """With every shard smaller than a batch among a round's winners, the
    faults read far above the program's own readings (at the cell's
    size such shards carry a few percent of a round's mass: PERF.md
    section 2)."""
    _, _, fleet, cases = measured
    program = max(REF.compare(fleet, inp, got)["update_gap"]
                  for inp, got in cases)
    small = (fleet.sizes > 0) & (fleet.sizes < 32)
    assert small.any()
    fault = max(REF.compare(fleet, inp, masked_batch_fault(
        fleet, inp, dataclasses.replace(got, win=got.win | small), kind)
    )["update_gap"] for inp, got in cases)
    assert fault > 10 * program, (fault, program)


def test_too_many_class_programs_stop_the_run_before_stage_1(
        tmp_path, monkeypatch):
    monkeypatch.setattr(DRIVER, "MAX_PROGRAMS", 1)
    monkeypatch.setattr(DRIVER.FederatedServer, "cluster", lambda self: (
        pytest.fail("stage 1 ran")))
    with pytest.raises(RuntimeError, match="class programs"):
        DRIVER.measure(small_cell(tmp_path))


@pytest.mark.parametrize("seed", range(6))
def test_pick_checked_takes_the_eval_rounds_first(seed):
    rng = np.random.default_rng(seed)
    is_eval = rng.random(40 + seed) < 0.11
    is_eval[rng.integers(is_eval.size)] = True
    picked = DRIVER.pick_checked(np.random.default_rng([seed, 7]), is_eval,
                                 12, 2)
    assert len(picked) == len(set(picked)) == 12
    assert is_eval[picked].sum() >= min(2, is_eval.sum())
    assert picked == sorted(picked)
