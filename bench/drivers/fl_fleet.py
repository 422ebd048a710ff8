"""Driver ``fl_fleet``: a cross-device federated training job on the
``device`` runtime, driven round by round through ``FederatedServer``.

It is the ``fl_rounds`` driver (set-up, the snapshot after stage 1, the
warm job, back-to-back jobs of ``rounds_per_job`` rounds, the reference
check, the operand probe: all imported from there) at fleet scale, where
two of its choices do not hold:

* the traced job runs ``trace_rounds`` rounds, not a whole job: a round
  of a few hundred winners under the profiler is long, and a traced job
  of ``rounds_per_job`` such rounds would outlast the run;
* with an eval every ``eval_every`` rounds most rounds carry none, so
  ``check_evals`` of the ``check_rounds`` checked rounds are drawn from
  the window's eval rounds and the rest from all of its other rounds;
  ``eval_loss_gap`` always has a reading.

Set-up compiles every (class, tier) program of the fleet store, cold in a
checkout's first run: about 13 s each on one v5e.  A program that would
warm more than ``MAX_PROGRAMS`` of them cannot set up this cell within 20
minutes, so the run stops as soon as the server is built, before stage
1, with an error that names the count.

The set-up lines on standard error also name the (class, tier) programs
that the warm-up compiled, and the seconds spent compiling so far.
"""
from __future__ import annotations

import dataclasses
import shutil
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, trace as TR
from bench.drivers.fl_rounds import (MAX_JOBS, TA, Job, _fl_config,
                                     _progress, _stream, check,
                                     initial_energy, operand_probe,
                                     train_flops)
from bench.gen.images import make_images, seed_key
from bench.gen.partition import partition
from bench.harness import Cell, Result
from bench.reference import cnn_mnist as M
from bench.reference import fl as REF

from repro import obs
from repro.core.adapters import cnn_adapter
from repro.core.server import FederatedServer
from repro.obs.sinks import MemorySink

# (class, tier) programs a cold set-up of this cell can compile
MAX_PROGRAMS = 40


def run(cell: Cell) -> Result:
    """``fl_rounds.run`` over this driver's :func:`measure`."""
    res, fleet, cases = measure(cell)
    t = time.perf_counter()
    stated = cell.config["matmul_operands"]
    gaps = operand_probe(fleet, cases[0][0].params)
    _progress("matmul operands probed: program's logits against the "
              "reference with operands in " + ", ".join(
                  f"{k} {v:.3e}" for k, v in gaps.items()), t)
    if min(gaps, key=gaps.get) != stated or any(
            gaps[stated] * 10 > v for k, v in gaps.items() if k != stated):
        raise RuntimeError(
            f"the program does not round matmul operands to {stated}, as "
            f"the configuration states: {gaps}")
    res.checks = check(fleet, cases, cell.traffic["limits"])
    _progress(f"reference check of {len(cases)} rounds", t)
    return res


def pick_checked(rng: np.random.Generator, is_eval: np.ndarray,
                 rounds: int, evals: int) -> list:
    """Indices of the checked rounds: ``evals`` drawn from the rounds
    that evaluated, then the rest of ``rounds`` from all the others."""
    ev = np.flatnonzero(is_eval)
    first = rng.choice(ev, min(evals, ev.size), replace=False)
    others = np.setdiff1d(np.arange(is_eval.size), first)
    rest = rng.choice(others, min(rounds - first.size, others.size),
                      replace=False)
    return sorted(int(i) for i in np.concatenate([first, rest]))


def measure(cell: Cell):
    """Set up, run the window and collect the checked rounds: returns
    ``(result without checks, fleet, [(inputs, answers)])``."""
    cfg_file, traffic, seed = cell.config, cell.traffic, cell.seed
    data, fl = cfg_file["data"], cfg_file["fl"]

    # -- inputs and weights from the seed --------------------------------
    t = time.perf_counter()
    xtr, ytr, xte_pool, yte_pool = make_images(seed, data["pool"],
                                               data["test_pool"])
    clients = partition(ytr, fl["num_clients"], data["num_classes"],
                        fl["non_iid_level"], fl["imbalance_low"],
                        fl["imbalance_high"], seed)
    pick = _stream(seed, 1).choice(len(yte_pool), data["test_batch"],
                                   replace=False)
    xte, yte = xte_pool[pick], yte_pool[pick]
    fleet = REF.Fleet(xtr, ytr, [c.train_idx for c in clients], xte, yte,
                      fl, REF.OPERANDS[cfg_file["matmul_operands"]])
    cfg = _fl_config(cfg_file, traffic, seed)
    t = _progress("data and partition", t)

    srv = FederatedServer(cfg, cnn_adapter("mnist"), xtr, ytr, clients,
                          {"x": xte, "y": yte})
    srv.params = M.init(seed_key(seed, 3))
    srv.state = dataclasses.replace(srv.state, residual=jnp.asarray(
        initial_energy(fl, fl["num_clients"], _stream(seed, 2))))
    t = _progress("server", t)
    classes = srv.runtime.store.classes
    programs = sum(len(c.tiers) for c in classes)
    if programs > MAX_PROGRAMS:
        raise RuntimeError(
            f"the fleet store would warm {programs} class programs in "
            f"{len(classes)} classes, over the {MAX_PROGRAMS} that a cold "
            "set-up of this cell compiles within its time")

    # -- stage 1, snapshot, warm-up -------------------------------------
    srv.cluster()
    jax.block_until_ready(srv.state.clusters)
    stage1_s = time.perf_counter() - t
    labels = np.arange(fl["num_clients"]) % data["num_classes"]
    found = np.asarray(srv.state.clusters)
    held = sum(np.bincount(found[labels == c]).max()
               for c in range(min(data["num_classes"], len(labels))))
    t = _progress(f"stage 1 ({held} of {len(labels)} clients in their "
                  "label's most common cluster)", t)
    job = Job(srv, seed)
    srv.runtime.warmup(srv.params)
    st = obs.jax_stats.snapshot()
    t = _progress(
        f"warm-up of {programs} class programs "
        f"in {len(classes)} classes (compile_s so far "
        f"{st.get('compile_s', 0.0):.1f}, of it backend "
        f"{st.get('compile/backend_s', 0.0):.1f})", t)

    steps = []
    round_step = srv._round_step

    def recording_step(state, key):
        out = round_step(state, key)
        steps.append((state, key, out[0]))
        return out

    srv._round_step = recording_step
    job.run(MAX_JOBS, traffic["warm_rounds"])
    jax.block_until_ready(srv.params)
    steps.clear()
    t = _progress("warm job", t)

    sink = None
    if cell.trace:
        sink = MemorySink()
        obs.OBS.add_sink(sink)
    traces0 = obs.jax_stats.snapshot().get("traces", 0)

    # -- the window --------------------------------------------------------
    R = traffic["rounds_per_job"]
    job.starts.clear()
    job.records.clear()
    logs: Dict[int, list] = {}
    traced = None
    t_win = time.perf_counter()
    setup_s = t_win - cell.t_start
    deadline = t_win + cell.seconds
    j = 0
    while True:
        if cell.trace and traced is None and j >= 1:
            # a short job after the first, under the profiler
            prof_dir = cell.out_dir / f"trace-{cell.name}-{seed}"
            shutil.rmtree(prof_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(prof_dir), profiler_options=opts)
            t_obs0 = obs.now()
            with TA(TR.WINDOW):
                logs[j], _ = job.run(j, traffic["trace_rounds"])
                jax.block_until_ready(srv.params)
            t_obs1 = obs.now()
            jax.profiler.stop_trace()
            traced = (j, prof_dir, t_obs0, t_obs1)
            late = time.perf_counter() >= deadline
        else:
            logs[j], late = job.run(j, R, deadline)
        j += 1
        if late and (traced is not None or not cell.trace):
            break
    jax.block_until_ready(srv.params)
    t_end = time.perf_counter()
    retraces = obs.jax_stats.snapshot().get("traces", 0) - traces0
    records = job.records

    walls = np.diff(np.asarray(job.starts + [t_end]))
    n_rounds = len(records)
    e2e = {"setup_s": setup_s,
           "fl_rounds_per_s": n_rounds / (t_end - t_win),
           "fl_round_ms_p95": float(np.percentile(walls, 95)) * 1e3}
    done = sum(len(v) for v in logs.values())

    ctx = {"stage1_s": stage1_s, "retraces_in_window": retraces,
           "chips": cell.chips, "peak_flops": cell.peak_flops}
    if traced is not None:
        tj, prof_dir, t_obs0, t_obs1 = traced
        files = sorted(prof_dir.rglob("*.xplane.pb"))
        ctx["trace"] = TR.reduce_file(files[-1])
        shutil.rmtree(prof_dir, ignore_errors=True)
        ctx["rounds_traced"] = len(logs[tj])
        ctx["flops_traced"] = train_flops(
            fleet, logs[tj], fl["local_epochs"], data["test_batch"],
            flops.FWD_FLOPS[cfg_file["model"]]())
        ctx["host_assemble_s"] = sum(
            e["dur_s"] for e in sink.events
            if e.get("kind") == "span" and e["name"] == "cohort/assemble"
            and t_obs0 <= e["t0"] <= t_obs1)
        obs.OBS.reset()
    t = _progress(f"window ({len(records)} rounds)", t_win)
    stats = jax.devices()[0].memory_stats() or {}   # None on the CPU
    memory_peak = int(stats.get("peak_bytes_in_use", 0))

    # -- the check ---------------------------------------------------------
    is_eval = np.array([not logs[jj][tt].eval_skipped
                        for jj, tt, _, _ in records])
    picked = pick_checked(_stream(seed, 7), is_eval,
                          traffic["check_rounds"], traffic["check_evals"])
    cases = []
    for i in picked:
        jj, tt, p_in, p_out = records[i]
        state_in, key, state_out = steps[i]
        log = logs[jj][tt]
        win = np.zeros(fl["num_clients"], bool)
        win[log.selected] = True
        host = lambda tree: {k: np.asarray(v, np.float32)  # noqa: E731
                             for k, v in tree.items()}
        cases.append((
            REF.RoundInputs(
                clusters=np.asarray(state_in.clusters, np.int64),
                residual=np.asarray(state_in.residual, np.float32),
                history=np.asarray(state_in.history, np.int64),
                key=np.asarray(key, np.uint32), params=host(p_in)),
            REF.RoundAnswers(
                win=win, residual=np.asarray(state_out.residual, np.float32),
                history=np.asarray(state_out.history, np.int64),
                params=host(p_out),
                eval_loss=(None if log.eval_skipped
                           else float(log.test_loss)))))
    del srv, job, records, steps
    _progress(f"checked rounds gathered ({int(is_eval[picked].sum())} "
              "with an eval)", t)
    return Result(e2e=e2e, ctx=ctx, checks={}, attempted=n_rounds,
                  failed=n_rounds - done,
                  memory_peak_bytes=memory_peak), fleet, cases
