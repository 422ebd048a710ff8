"""Driver ``fl_rounds``: the paper's federated training job on the
``device`` runtime, driven round by round through ``FederatedServer``.

Set-up (all in ``setup_s``): the image pool, the partition, the initial
weights and batteries from the seed; the server; stage 1 (``cluster()``,
timed as ``stage1_s``); a snapshot of the state after stage 1; the
runtime's warm-up of every capacity-class program; one warm job, after
which the snapshot is restored.

The window runs back-to-back jobs of ``rounds_per_job`` rounds, each from
the snapshot with a round key made from ``(seed, job)``: selected clients
pay energy every round and an exhausted client never bids again, so a
window of one long job would drift into empty, cheap rounds.  A round is
one ``_dispatch_round(t, _eval_due(t, final))``, as ``run()`` makes it;
a job ends with ``_flush_pending()``, and the window with the first
flush after ``--seconds``, blocked on the weights.  Both are private
methods of the server: it has no public loop that leaves out stage 1.

A round's wall time runs from the start of its dispatch to the start of
the next one, so a job's last round carries its flush and the reset.

After the window, a sample of its rounds drawn from the seed is checked
against the plain reference (``bench.reference.fl``) from the inputs each
round started from.
"""
from __future__ import annotations

import dataclasses
import shutil
import sys
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, trace as TR
from bench.gen.images import make_images, seed_key
from bench.gen.partition import partition
from bench.harness import Cell, Result
from bench.reference import cnn_mnist as M
from bench.reference import fl as REF

from repro import obs
from repro.configs.base import FLConfig
from repro.core.adapters import cnn_adapter
from repro.core.server import FederatedServer
from repro.models.cnn import cnn_logits
from repro.obs.sinks import MemorySink

TA = jax.profiler.TraceAnnotation
MAX_JOBS = 1024


def _progress(what: str, t0: float) -> float:
    """One line on stderr per set-up phase; returns the clock."""
    now = time.perf_counter()
    print(f"bench: {what} {now - t0:.3f} s", file=sys.stderr, flush=True)
    return now


def _stream(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def initial_energy(fl: dict, n: int, rng: np.random.Generator):
    """Battery percent per client: the paper's case 2, a normal
    distribution truncated to [low, high] (drawn by rejection)."""
    mean, std = fl["init_energy_mean"], fl["init_energy_std"]
    lo, hi = fl["init_energy_low"], fl["init_energy_high"]
    out = np.empty(0)
    while out.size < n:
        d = rng.normal(mean, std, 2 * n)
        out = np.concatenate([out, d[(d >= lo) & (d <= hi)]])
    return (out[:n] * 100.0).astype(np.float32)


class Job:
    """The server after stage 1, what resets it to that point, and the
    rounds it has run since: ``starts`` (perf_counter at each dispatch)
    and ``records`` (job, round, weights in, weights out)."""

    def __init__(self, srv: FederatedServer, seed: int):
        self.srv = srv
        self.params, self.state = srv.params, srv.state
        self.history = srv._host_history.copy()
        base = seed_key(seed, 5)
        keys = jax.vmap(lambda j: jax.random.fold_in(base, j))(
            jnp.arange(MAX_JOBS + 1))
        self.keys = [keys[j] for j in range(MAX_JOBS + 1)]
        self.starts: List[float] = []
        self.records: List[tuple] = []

    def reset(self, job: int) -> None:
        srv = self.srv
        srv.params, srv.state = self.params, self.state
        srv.key = self.keys[job]
        srv._host_history = self.history.copy()
        srv.logs = []
        srv.total_client_reward = 0.0

    def run(self, job: int, rounds: int, deadline=None):
        """Dispatch up to ``rounds`` rounds of ``job`` and flush; stops
        early after the first round that ends past ``deadline``.  Returns
        the job's round logs and whether the deadline passed."""
        srv = self.srv
        with TA("bench/reset"):
            self.reset(job)
        late = False
        for t in range(rounds):
            final = t == rounds - 1
            p_in = srv.params
            self.starts.append(time.perf_counter())
            with TA("bench/dispatch"):
                srv._dispatch_round(t, srv._eval_due(t, final=final),
                                    final=final)
            self.records.append((job, t, p_in, srv.params))
            late = deadline is not None and time.perf_counter() >= deadline
            if late:
                break
        with TA("bench/flush"):
            srv._flush_pending()
        return srv.logs, late


def _fl_config(config: dict, traffic: dict, seed: int) -> FLConfig:
    fields = {f.name for f in dataclasses.fields(FLConfig)}
    kw = {k: v for k, v in config["fl"].items() if k in fields}
    server_seed = int(np.random.SeedSequence([seed, 9]).generate_state(1)[0]
                      >> 1)
    return FLConfig(**kw, runtime="device",
                    eval_every=traffic["eval_every"], seed=server_seed)


def train_flops(fleet: REF.Fleet, logs, epochs: int, test_batch: int,
                fwd: int) -> float:
    """Model FLOPs of the real work of the logged rounds: three forward
    passes per sample of every full minibatch the winners ran, one per
    test sample of every eval."""
    n = fleet.sizes
    bs = np.minimum(32, n)
    samples = np.where(n > 0, (n // np.maximum(bs, 1)) * bs, 0) * epochs
    total = 0.0
    for log in logs:
        total += flops.TRAIN_PER_FWD * fwd * float(samples[log.selected].sum())
        if not log.eval_skipped:
            total += fwd * test_batch
    return total


def run(cell: Cell) -> Result:
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
    t = _progress("warm-up of the class programs", t)

    steps: List[tuple] = []
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
            # the job after the first under the profiler
            prof_dir = cell.out_dir / f"trace-{cell.name}-{seed}"
            shutil.rmtree(prof_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(prof_dir), profiler_options=opts)
            t_obs0 = obs.now()
            with TA(TR.WINDOW):
                logs[j], _ = job.run(j, R)
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
    rng = _stream(seed, 7)
    picked = sorted(int(i) for i in rng.choice(
        len(records), min(traffic["check_rounds"], len(records)),
        replace=False))
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
    _progress("checked rounds gathered", t)
    return Result(e2e=e2e, ctx=ctx, checks={}, attempted=n_rounds,
                  failed=n_rounds - done,
                  memory_peak_bytes=memory_peak), fleet, cases


def operand_probe(fleet: REF.Fleet, params) -> Dict[str, float]:
    """How the program rounds the operands of its products, on the device
    the window ran on: the mean gap of the program's own CNN logits over
    the test batch from the reference's, with the operands rounded to
    each type the reference knows."""
    x = jnp.asarray(fleet.x_test)
    got = np.asarray(jax.jit(cnn_logits, static_argnums=2)(
        jax.device_put(params), x, "mnist"), np.float64)
    return {name: float(np.mean(np.abs(
        got - REF.logits(fleet, params, ops))))
        for name, ops in REF.OPERANDS.items()}


def check(fleet: REF.Fleet, cases, limits: Dict[str, float]):
    """Each number's largest reading over the checked rounds, with its
    limit."""
    worst: Dict[str, float] = {}
    for inp, got in cases:
        for k, v in REF.compare(fleet, inp, got).items():
            worst[k] = max(worst.get(k, 0.0), v)
    missing = set(limits) - set(worst)
    if missing:
        raise RuntimeError(f"no reading of {sorted(missing)} in the "
                           "checked rounds")
    return {k: (worst[k], float(limits[k])) for k in limits}
