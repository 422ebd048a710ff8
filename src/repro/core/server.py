"""Federated server: the full Algorithm 1 loop.

Stage 1  (once)    : gradient/weight clustering of all clients.
Stage 2  (per round): cost -> Nash bids -> s_min threshold -> per-cluster
                      winners (or the paper's baselines' random picks),
                      rewards, energy/history update and round metrics —
                      fused into ONE jitted program per round
                      (repro.core.rounds), one host transfer for logging.
Stage 3  (per round): winners run I local epochs (FedAvg local SGD, or
                      FedProx with the proximal term), server aggregates
                      w_{t+1} = sum_k p_k w^k_{t+1}, energy/history update.

Stage-3 execution is delegated to a pluggable :mod:`repro.sim` cohort
runtime (``cfg.runtime``): ``sequential`` runs clients one by one (the
paper's own execution model, kept as the reference oracle), ``vectorized``
runs the whole cohort as one compiled vmap/scan program per size bucket,
``sharded`` maps it over the cohort mesh, and ``device`` keeps the whole
fleet resident on device (repro.sim.fleet) so per-round assembly is an
on-device gather; the *launch* layer additionally maps cohorts onto mesh
axes for the TPU-scale path — see repro/launch/train.py.

The round loop is ASYNC: each round's control-plane metrics (and its
eval scalars, computed every ``cfg.eval_every`` rounds by one fused
jitted accuracy+loss program) stay on device in a pending buffer, and
round t+1's selection/training dispatch while round t's fetches are
still in flight.  One batched ``device_get`` drains the buffer at
logging boundaries (verbose prints, ``run_round`` returns, end of run) —
the only unconditional per-round host transfer left is the winner mask,
which stage-3's host-seeded shuffle rng genuinely needs.

With fleet dynamics on (``cfg.dynamics_enabled`` — any churn or a
positive deadline) the fused round step additionally runs the
repro.sim.dynamics fault model, and the aggregation path degrades
gracefully instead of assuming a full cohort: only COMPLETED winners
(plus retry-or-replace substitutes for DROPPED ones) aggregate
synchronously — FedAvg re-weights over the survivors automatically
because the cohort runtimes normalize within whatever index set they
are handed; a zero-survivor round leaves the params untouched and logs
a ``round/empty`` dynamics event (never a 0/0).  Under ``--aggregation
buffered`` LATE winners still train, but their update lands in a
device-resident buffer as a staleness-stamped delta and folds into the
global model FedBuff-style at goal-count or timeout boundaries
(``round/buffer_fold`` spans).  With dynamics off both aggregation
modes take the exact pre-dynamics code path — the synchronous oracle —
so churn-0 runs stay bit-identical (tests/test_dynamics.py).
"""
from __future__ import annotations

import json
import os
from collections import deque
from copy import deepcopy
from dataclasses import dataclass, replace as dc_replace
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import FLConfig
from repro.core import aggregation as AGG
from repro.core import clustering as CL
from repro.core import energy as EN
from repro.core import rounds as RND
from repro.core import schemes as SCH
from repro.core import selection as SEL
from repro.core.adapters import ModelAdapter
from repro.optim import apply_updates, sgd
from repro.sim import dynamics as DYN
from repro.sim.runtime import make_runtime


@dataclass
class RoundLog:
    round: int
    selected: np.ndarray
    test_acc: float            # NaN on rounds skipped by cfg.eval_every
    test_loss: float
    energy_std: float
    mean_bid: float
    server_reward: float
    client_reward_sum: float
    vds_gap: float
    # True when the round was off the eval cadence (test_acc NaN by
    # design); a NaN with eval_skipped=False means the eval RAN and the
    # model diverged — the two cases were indistinguishable before
    eval_skipped: bool = False


@dataclass
class _PendingRound:
    """A dispatched round whose host fetches haven't happened yet:
    ``metrics`` is the round step's on-device scalar dict, ``eval_pair``
    the fused (accuracy, loss) device scalars or None off-cadence,
    ``dyn`` the host-side dynamics scalars (replacements, buffer depth)
    or None with dynamics off."""

    round: int
    selected: np.ndarray
    metrics: Any
    eval_pair: Optional[Any]
    dyn: Optional[Dict[str, float]] = None
    # screened-aggregation reports (device scalar dicts) for every
    # defended sub-cohort this round dispatched (late first, main last);
    # they drain with the same batched fetch as the metrics
    defense: Optional[List[Any]] = None


@dataclass
class _BufferedUpdate:
    """One late update parked in the device-resident FedBuff buffer:
    ``delta`` is the late sub-cohort's aggregated param delta (vs the
    globals it trained from) as a device tree, ``mass`` its data mass
    (sum of local sizes — the FedAvg numerator it would have carried),
    ``round`` the dispatch round and ``arrival`` the first round the
    server can fold it (dispatch + 1: late means after the deadline)."""

    delta: Any
    mass: float
    round: int
    arrival: int
    # fraction of the sub-cohort's rows that survived screening (device
    # scalar from the screened report), or None undefended.  The fold
    # scales the entry's mass by it so a fully-quarantined late cohort
    # contributes ZERO mass — its (zeroed) delta must not dilute the
    # fold, and an all-quarantined buffer must not divide by zero.
    mass_scale: Any = None


@dataclass
class _RingEntry:
    """One watchdog ring snapshot: ``tree`` is exactly the
    :meth:`FederatedServer._ckpt_tree` pytree (params, selection state,
    key chain, defense state, server LR — the PR 8 checkpoint format,
    held on device instead of disk; JAX arrays are immutable so the refs
    ARE the snapshot), plus the host-side state a rollback must restore
    verbatim."""

    round: int
    tree: Dict[str, Any]
    reward: float
    last_eval: Tuple[float, float]
    dyn_rng_state: Optional[dict] = None
    host_avail: Optional[np.ndarray] = None


# device metric keys the dynamics round step adds; drained with the same
# batched fetch as the base metrics and mirrored into the round series
_DYN_METRIC_KEYS = ("num_completed", "num_late", "num_dropped",
                    "staleness_mean", "staleness_max", "mean_latency",
                    "num_avail")

# device metric keys the defended round step adds (repro.core.rounds
# emits them only when SelectionState carries strikes; trust_* is the
# continuous reputation score the pricing mode bids against)
_DEF_METRIC_KEYS = ("num_banned", "trust_mean", "trust_min")

# device metric keys the selection-scheme zoo adds: fairness_hist_std
# comes from every scheme; the budget_* ledger scalars only from
# scheme_state-bearing schemes (longterm_auction), the latency ones only
# from fedcs — all drained with the same batched fetch
_SCHEME_METRIC_KEYS = ("fairness_hist_std", "budget_spent",
                       "budget_remaining", "budget_queue",
                       "pred_latency_mean", "num_feasible")


class FederatedServer:
    def __init__(self, cfg: FLConfig, adapter: ModelAdapter,
                 x: np.ndarray, y: np.ndarray, clients,
                 test_batch: Dict[str, np.ndarray],
                 assign_fn=None, seed: Optional[int] = None):
        self.cfg = cfg
        self.adapter = adapter
        self.x, self.y = x, y
        self.clients = clients
        self.test_batch = test_batch
        self.assign_fn = assign_fn
        self.key = jax.random.PRNGKey(cfg.seed if seed is None else seed)
        self.params = adapter.init(self._next_key())
        self.logs: List[RoundLog] = []
        self.runtime = make_runtime(cfg, adapter, x, y, clients)

        sizes = jnp.asarray([c.size for c in clients], jnp.int32)
        self.dynamics = cfg.dynamics_enabled
        self.defended = cfg.defended
        self.state = SEL.SelectionState(
            clusters=jnp.zeros((cfg.num_clients,), jnp.int32),
            residual=EN.init_energy(cfg, self._next_key()),
            history=jnp.zeros((cfg.num_clients,), jnp.int32),
            local_sizes=sizes,
            # None with dynamics off: the field must not exist as an
            # array leaf or the dynamics-free round traces would change
            staleness=(jnp.zeros((cfg.num_clients,), jnp.int32)
                       if self.dynamics else None),
            # same rule for the reputation ledger with defenses off
            strikes=(jnp.zeros((cfg.num_clients,), jnp.float32)
                     if self.defended else None),
            # per-scheme carried state (None for stateless schemes —
            # same Optional-last-field rule as staleness/strikes)
            scheme_state=SCH.init_scheme_state(cfg),
        )
        from repro.core.virtual_dataset import client_count_histograms
        from repro.data.partition import global_histogram
        self.global_hist = global_histogram(y, cfg.num_classes)
        self.client_labels = [y[c.train_idx] for c in clients]
        self.total_client_reward = 0.0
        # fused round control plane: one jitted (state, key) -> (state,
        # win, metrics) program; metrics (energy std, mean winning bid,
        # reward sums, vds-gap) are computed on device so run_round does
        # one host transfer for the whole control plane.
        self._round_step = RND.make_round_step(
            cfg, client_count_histograms(self.client_labels,
                                         cfg.num_classes),
            self.global_hist, dynamics=self.dynamics)
        if self.dynamics:
            # the DEDICATED dynamics chain: split off its own root so
            # churn-0 runs consume the selection chain identically
            self._dyn_key = DYN.dynamics_key(cfg)
            self.dyn_state = DYN.init_dynamics(cfg)
            # host mirrors the replacement sampler reads: round-start
            # availability and (after stage 1) cluster ids
            self._host_avail = np.ones((cfg.num_clients,), bool)
            self._host_clusters = np.zeros((cfg.num_clients,), np.int64)
            self._host_sizes = np.asarray([c.size for c in clients],
                                          np.int64)
            # replacement draws come from their own host rng chain, so
            # they are a pure function of (seed, outcome stream) and
            # identical across cohort runtimes
            self._dyn_rng = np.random.default_rng(
                np.uint32(cfg.seed) + 0x5D7A)
            # scheme-aware replacement constraint (fedcs: substitutes
            # must themselves be plausibly deadline-feasible); None from
            # the registry means unconstrained
            m = SCH.host_replacement_mask(cfg, self._host_sizes)
            self._host_feasible = (np.ones((cfg.num_clients,), bool)
                                   if m is None else np.asarray(m, bool))
            self.outcome_log: List[np.ndarray] = []   # per-round winner codes
            self._late_buffer: List[_BufferedUpdate] = []
            self._delta_step = jax.jit(
                lambda new, old: jax.tree.map(jnp.subtract, new, old))
            self._fold_one = jax.jit(
                lambda p, d, c: jax.tree.map(lambda a, b: a + c * b, p, d))
        if self.defended:
            # Byzantine-tolerant stage 3 (repro.core.aggregation): the
            # adversary chain + population mask are frozen at init (both
            # pure functions of cfg — identical across runtimes and
            # resumes); one screened program handles every cohort size by
            # padding rows up to the static capacity
            self._adv_root = DYN.adversary_key(cfg)
            self._adv_mask = np.asarray(
                obs.device_get(DYN.adversary_mask(cfg)), bool)
            self._screen_cap = AGG.screen_capacity(cfg)
            self._screen_step = AGG.make_screened_step(cfg)
            self._apply_delta = AGG.make_apply_delta(self.params)
            # jitted so the warm loop never runs eager index/key ops —
            # those materialize scalar constants via implicit h2d
            # transfers, which the sync auditor rejects
            self._gather_rows = jax.jit(
                lambda d, i: jnp.take(d, i, axis=0, mode="clip"))
            self._fold_key = jax.jit(jax.random.fold_in)
            # running defense statistics (clip EMA + adaptive MAD band /
            # pressure when --defense-mode adaptive, tighten factor when
            # the watchdog is on); stays on device between rounds
            self._defense_state = AGG.init_defense_state(cfg)
            # host tallies filled at flush boundaries (launch summary)
            self.defense_totals: Dict[str, int] = {"quarantined": 0,
                                                   "screened": 0,
                                                   "banned_final": 0}
        self._watchdog = cfg.watchdog_enabled
        if self._watchdog:
            # divergence watchdog: ring of the last K healthy snapshots
            # (each a _ckpt_tree pytree — the checkpoint format, held on
            # device), a detector over the drained eval stream, and a
            # rollback policy that restores the newest healthy entry,
            # tightens the defense and decays the server LR
            self._wd_ring: deque = deque(
                maxlen=max(int(cfg.watchdog_ring), 1))
            self._srv_lr = jnp.float32(1.0)
            self._wd_loss_ema: Optional[float] = None
            self._wd_acc_peak = float("-inf")
            self._wd_healthy = False      # healthy eval since last rollback
            self._wd_rollbacks = 0
            self.watchdog_totals: Dict[str, int] = {"rollbacks": 0,
                                                    "snapshots": 0}
            # server LR enters as a bit-exact no-op at lr=1.0: the delta
            # path scales by exactly 1.0 (IEEE identity) and the blend
            # `b + (s-1)*(b-a)` adds exactly 0.0 — a watchdog-on run
            # that never rolls back matches watchdog-off numerically
            self._scale_delta = jax.jit(lambda a, s: a * s)
            self._wd_blend = jax.jit(
                lambda p0, p1, s: jax.tree.map(
                    lambda a, b: b + (s - 1.0) * (b - a), p0, p1))
        # host mirror of participation counts: stage-3 shuffle seeding
        # reads history per winner, which on the device array cost one
        # int(history[i]) sync per client per round.
        self._host_history = np.zeros((cfg.num_clients,), np.int64)
        # fused eval: accuracy + loss as ONE jitted program (the two
        # nested jits inline), so an eval round costs one deferred fetch
        # instead of two blocking ones; the test batch is committed to
        # device once instead of being re-transferred per round.
        def _eval(p, b):
            obs.jax_stats.note_trace("eval")     # trace-time side effect
            return adapter.accuracy(p, b), adapter.loss(p, b)

        self._eval_step = jax.jit(_eval)
        self._test_dev = obs.device_put(test_batch)
        self._pending: List[_PendingRound] = []
        # last eval pair actually drained (progress prints show this
        # instead of forcing an off-cadence eval — see run())
        self._last_eval = (float("nan"), float("nan"))

    # ------------------------------------------------------------------
    def _next_key(self):
        self.key, k = jax.random.split(self.key)
        return k

    def _next_dyn_key(self):
        self._dyn_key, k = jax.random.split(self._dyn_key)
        return k

    # ------------------------------------------------------------------
    def cluster(self):
        """Stage 1: cluster clients (scheme-dependent feature).

        With the default ``assign_fn=None`` k-means routes through the
        fused clustering engine (repro.core.clustering.kmeans): one jit
        for seeding + Lloyd + restart-argmin, the Pallas assign+update
        kernel on TPU and its jnp twin elsewhere; ``assign_fn`` overrides
        assignment only (testing hook)."""
        cfg = self.cfg
        if cfg.scheme == "random":
            return
        feature_kind = ("weights" if cfg.scheme == "weights_cluster_random"
                        else "gradient")
        data = [(self.x[c.train_idx], self.y[c.train_idx])
                for c in self.clients]

        def local_steps_fn(params, x, y, key):
            # Wang et al. [2] feature: local model delta after 1 epoch SGD
            init, upd = sgd(cfg.lr)
            opt = init(params)
            p = params
            bs = min(32, x.shape[0])
            for i in range(0, x.shape[0] - bs + 1, bs):
                b = {"x": x[i:i + bs], "y": y[i:i + bs]}
                g = self.adapter.grad(p, b)
                u, opt = upd(g, opt, p)
                p = apply_updates(p, u)
            delta = jax.tree.map(lambda a, b: (a - b).reshape(-1), p, params)
            return jnp.concatenate(jax.tree.leaves(delta))

        key = self._next_key()
        # the runtime may compute the whole feature pass as one batched
        # program (vectorized backend); None -> reference per-client loop
        feats = self.runtime.cluster_features(self.params, key, feature_kind)
        labels, cent, feats = CL.cluster_clients(
            self.adapter.grad, self.params, data, cfg, key,
            feature_kind=feature_kind, local_steps_fn=local_steps_fn,
            assign_fn=self.assign_fn, precomputed_feats=feats)
        self.state = SEL.SelectionState(
            clusters=labels.astype(jnp.int32), residual=self.state.residual,
            history=self.state.history, local_sizes=self.state.local_sizes,
            staleness=self.state.staleness, strikes=self.state.strikes,
            scheme_state=self.state.scheme_state)
        if self.dynamics:
            self._host_clusters = np.asarray(obs.device_get(labels),
                                             np.int64)

    # ------------------------------------------------------------------
    def local_train(self, client_idx: int, global_params):
        return self.runtime.train_client(
            global_params, client_idx, int(self._host_history[client_idx]))

    # -- defended aggregation ------------------------------------------
    def _train_defended(self, params0, train_idx: np.ndarray, t: int,
                        chan: int, strikes):
        """Defended stage 3: the runtime returns the cohort's per-client
        flat deltas, the fused screened program (repro.core.aggregation)
        corrupts (adversary model), quarantines, defends, aggregates and
        updates the reputation ledger in one call, and the screened
        aggregate delta is applied to ``params0``.  ``chan`` separates
        the per-round adversary key of the main (0) and buffered-late
        (1) sub-cohorts so their corruption draws never collide.
        Returns ``(new_params, report, new_strikes)`` — all None for an
        empty cohort (strikes pass through unchanged)."""
        upd = self.runtime.train_cohort_updates(params0, train_idx,
                                                self._host_history)
        if upd is None:
            return None, None, strikes
        ids = np.asarray(upd.client_idx, np.int32)
        real = np.flatnonzero(ids >= 0).astype(np.int32)
        if real.size == 0:
            return None, None, strikes
        cap = self._screen_cap
        while cap < real.size:     # never hit: capacity bounds the cohort
            cap *= 2
        # compact the runtimes' padding rows out and pad to the one
        # static capacity in a single on-device gather driven by a
        # host-built index plan (padding slots gather row 0 and are
        # masked by valid=False), so the screened program compiles
        # exactly once and the warm loop's only h2d traffic is these
        # explicit, counted plan arrays — no eager fill constants, which
        # the sync auditor (correctly) rejects as implicit transfers
        gidx = np.zeros((cap,), np.int32)
        gidx[:real.size] = real
        w = np.zeros((cap,), np.float32)
        w[:real.size] = np.asarray(upd.weights, np.float32)[real]
        idp = np.full((cap,), -1, np.int32)
        idp[:real.size] = ids[real]
        valid = idp >= 0
        adv = valid & self._adv_mask[np.clip(idp, 0, None)]
        gd, wd, vd, ad, idd, rnd, fold = obs.device_put(
            (gidx, w, valid, adv, idp, np.int32(t),
             np.uint32(2 * t + chan + 1)))
        dpad = self._gather_rows(upd.deltas, gd)
        key = self._fold_key(self._adv_root, fold)
        agg, new_strikes, self._defense_state, report = self._screen_step(
            dpad, wd, vd, ad, idd, strikes, self._defense_state, rnd, key)
        if self._watchdog:
            # server LR (decayed by rollbacks): exact no-op at 1.0
            agg = self._scale_delta(agg, self._srv_lr)
        return self._apply_delta(params0, agg), report, new_strikes

    # ------------------------------------------------------------------
    def _eval_due(self, t: int, final: bool = False) -> bool:
        return final or self.cfg.eval_every <= 1 \
            or t % self.cfg.eval_every == 0

    def _dispatch_round(self, t: int, eval_now: bool,
                        final: bool = False) -> None:
        """Dispatch one FL round without fetching its results.  The whole
        stage-2 control plane (selection, rewards, energy/history update,
        round metrics) is one jitted call (repro.core.rounds
        .make_round_step); only the winner mask is fetched — stage-3's
        host-seeded shuffle rng needs it — while the metric scalars (and
        the fused eval pair, when due) stay on device in the pending
        buffer until the next logging boundary.  With fleet dynamics on
        the fused step also runs the fault model and dispatch degrades
        gracefully over the outcome mask (:meth:`_dispatch_round_dyn`)."""
        if self.dynamics:
            return self._dispatch_round_dyn(t, eval_now, final)
        with obs.span("round/dispatch", round=t):
            with obs.span("round/select", round=t):
                new_state, win, metrics = self._round_step(self.state,
                                                           self._next_key())
                # the one unconditional per-round fetch (explicit, counted)
                with obs.span("round/winner_fetch", round=t):
                    win_np = obs.device_get(win)
                sel_idx = np.nonzero(win_np)[0]

            # stage 3: local training + aggregation (cohort runtime
            # backend); shuffle seeds read the pre-round host history
            # mirror
            defense: Optional[List[Any]] = None
            with obs.span("round/train", round=t,
                          cohort=int(sel_idx.size)):
                if self.defended:
                    new_params, rep, strikes = self._train_defended(
                        self.params, sel_idx, t, 0, new_state.strikes)
                    new_state = dc_replace(new_state, strikes=strikes)
                    if rep is not None:
                        defense = [rep]
                else:
                    new_params = self.runtime.train_cohort(
                        self.params, sel_idx, self._host_history)
                    if new_params is not None and self._watchdog:
                        new_params = self._wd_blend(self.params, new_params,
                                                    self._srv_lr)
            if new_params is not None:
                self.params = new_params
            else:
                # zero-winner (or all-zero-size) round: the runtimes
                # return None instead of a 0/0 aggregate — params pass
                # through unchanged and the event is visible in the log
                self._log_empty_round(t)

            self.state = new_state
            self._host_history[sel_idx] += 1
            if eval_now:
                with obs.span("round/eval", round=t):
                    ev = self._eval_step(self.params, self._test_dev)
            else:
                ev = None
            self._pending.append(_PendingRound(
                round=t, selected=sel_idx, metrics=metrics, eval_pair=ev,
                defense=defense))

    # -- fleet dynamics ------------------------------------------------
    def _log_empty_round(self, t: int) -> None:
        """A round whose synchronous aggregate had no survivors: params
        pass through unchanged (never a division by a zero weight sum)
        and the event lands in the log for the schema validator."""
        obs.OBS.counter("round/empty")
        obs.OBS.event("dynamics", name="round/empty", round=t)

    def _resample_dropped(self, dropped: np.ndarray,
                          win_np: np.ndarray) -> np.ndarray:
        """Retry-or-replace: each DROPPED winner's slot is refilled by a
        uniform draw among its cluster's currently-available non-winners
        with local data (an empty candidate pool forfeits the slot).
        Draws come from the dedicated host dynamics rng, so replacement
        picks are a pure function of (seed, outcome stream) — identical
        across cohort runtimes.  Under ``--scheme-select fedcs`` the
        candidate pool is further restricted to plausibly
        deadline-feasible clients (schemes.host_replacement_mask) — a
        substitute that can't meet the deadline would just convert the
        DROPPED slot into a LATE one."""
        chosen: List[int] = []
        taken = win_np.copy()
        for gid in dropped:
            cand = np.nonzero(
                (self._host_clusters == self._host_clusters[int(gid)])
                & self._host_avail & ~taken & (self._host_sizes > 0)
                & self._host_feasible)[0]
            if cand.size == 0:
                continue
            pick = int(cand[self._dyn_rng.integers(cand.size)])
            taken[pick] = True
            chosen.append(pick)
        return np.asarray(chosen, np.int64)

    def _maybe_fold_buffer(self, t: int, force: bool = False) -> int:
        """Fold the arrived late updates into the global model when the
        FedBuff boundary hits: goal-count reached, the oldest arrived
        entry timed out, or ``force`` (the final round folds whatever has
        arrived; updates still in flight when the run ends are lost —
        they never reached the server).  Each entry's delta is scaled by
        its staleness discount times its share of the folded data mass,
        so the fold is a staleness-weighted FedAvg over the buffer."""
        arrived = [e for e in self._late_buffer if e.arrival <= t]
        if not arrived:
            return 0
        oldest = min(e.round for e in arrived)
        if not (force or len(arrived) >= self.cfg.buffer_goal
                or t - oldest >= self.cfg.buffer_timeout):
            return 0
        # defended entries carry their screening survivor fraction as a
        # device scalar; one explicit (counted) fetch scales the masses
        # so quarantined rows carry no weight in the fold
        if any(e.mass_scale is not None for e in arrived):
            scales = obs.device_get(
                [e.mass_scale if e.mass_scale is not None
                 else np.float32(1.0) for e in arrived])
            masses = [e.mass * float(s) for e, s in zip(arrived, scales)]
        else:
            masses = [e.mass for e in arrived]
        total = sum(masses)
        if total <= 0.0:
            # every arrived row was quarantined: the buffered deltas are
            # all screened-to-zero — drop them loudly instead of folding
            # a 0/0 into the params
            self._late_buffer = [e for e in self._late_buffer
                                 if e.arrival > t]
            obs.OBS.counter("dyn/buffer_all_quarantined")
            obs.OBS.event("dynamics", name="buffer/all_quarantined",
                          round=t, entries=len(arrived))
            return 0
        with obs.span("round/buffer_fold", round=t, entries=len(arrived)):
            p = self.params
            for e, mass in zip(arrived, masses):
                c = (DYN.staleness_weight(self.cfg, t - e.round)
                     * mass / total)
                p = self._fold_one(p, e.delta, c)
            self.params = p
        self._late_buffer = [e for e in self._late_buffer
                             if e.arrival > t]
        obs.OBS.counter("dyn/buffer_folds")
        obs.OBS.event("dynamics", name="buffer/fold", round=t,
                      entries=len(arrived), oldest=oldest)
        return len(arrived)

    def _dispatch_round_dyn(self, t: int, eval_now: bool,
                            final: bool = False) -> None:
        """The dynamics-aware dispatch: one fused (selection + fault
        model) step, then aggregation over the outcome mask — COMPLETED
        winners plus retry-or-replace substitutes aggregate now (FedAvg
        re-weights over them automatically), LATE winners feed the
        buffered path, DROPPED ones only burned energy.  The extra host
        traffic vs the dynamics-free loop is one batched fetch of the
        outcome codes + next availability mask alongside the winner
        mask."""
        cfg = self.cfg
        with obs.span("round/dispatch", round=t):
            with obs.span("round/select", round=t):
                (new_state, new_dyn, win, outcome,
                 metrics) = self._round_step(self.state, self.dyn_state,
                                             self._next_key(),
                                             self._next_dyn_key())
                with obs.span("round/winner_fetch", round=t):
                    win_np, out_np, next_avail = obs.device_get(
                        (win, outcome, new_dyn.avail))
                sel_idx = np.nonzero(win_np)[0]
            completed, late, dropped = DYN.split_outcomes(sel_idx, out_np)
            self.outcome_log.append(out_np[sel_idx])
            repl = (self._resample_dropped(dropped, win_np)
                    if cfg.replace_dropped and dropped.size
                    else np.empty((0,), np.int64))
            train_idx = np.concatenate(
                [completed.astype(np.int64), repl])
            dyn_row: Dict[str, float] = {"num_replaced": int(repl.size)}
            if dropped.size:
                obs.OBS.counter("dyn/dropped", int(dropped.size))
            if late.size:
                obs.OBS.counter("dyn/deadline_miss", int(late.size))
            if repl.size:
                obs.OBS.counter("dyn/replaced", int(repl.size))

            params0 = self.params
            buffered = cfg.aggregation == "buffered"
            defense: List[Any] = []
            if buffered and late.size:
                # the late sub-cohort trains from the same globals it was
                # dispatched with; its aggregate becomes a buffered delta
                with obs.span("round/train_late", round=t,
                              cohort=int(late.size)):
                    if self.defended:
                        late_agg, rep, strikes = self._train_defended(
                            params0, late, t, 1, new_state.strikes)
                        new_state = dc_replace(new_state, strikes=strikes)
                        if rep is not None:
                            defense.append(rep)
                    else:
                        late_agg = self.runtime.train_cohort(
                            params0, late, self._host_history)
                if late_agg is not None:
                    self._late_buffer.append(_BufferedUpdate(
                        delta=self._delta_step(late_agg, params0),
                        mass=float(self._host_sizes[late].sum()),
                        round=t, arrival=t + 1,
                        # survivor fraction rides as a device scalar and
                        # is fetched at fold time: a fully-quarantined
                        # late cohort must fold with zero mass
                        mass_scale=(rep["survivor_frac"]
                                    if self.defended and rep is not None
                                    else None)))
            with obs.span("round/train", round=t,
                          cohort=int(train_idx.size)):
                if self.defended:
                    new_params, rep, strikes = self._train_defended(
                        params0, train_idx, t, 0, new_state.strikes)
                    new_state = dc_replace(new_state, strikes=strikes)
                    if rep is not None:
                        defense.append(rep)
                else:
                    new_params = self.runtime.train_cohort(
                        params0, train_idx, self._host_history)
                    if new_params is not None and self._watchdog:
                        new_params = self._wd_blend(params0, new_params,
                                                    self._srv_lr)
            if new_params is not None:
                self.params = new_params
            else:
                self._log_empty_round(t)

            self.state = new_state
            self.dyn_state = new_dyn
            self._host_avail = np.asarray(next_avail, bool)
            # the shuffle-seed mirror advances for every client whose
            # local pass actually ran this round (survivors, substitutes
            # and — under buffering — the late trainers); the device-side
            # history keeps the control plane's commitment accounting
            trained = (np.concatenate([train_idx, late.astype(np.int64)])
                       if buffered else train_idx)
            self._host_history[trained] += 1
            folded = self._maybe_fold_buffer(t, force=final)
            dyn_row["buffer_len"] = len(self._late_buffer)
            dyn_row["buffer_folded"] = folded
            if eval_now:
                with obs.span("round/eval", round=t):
                    ev = self._eval_step(self.params, self._test_dev)
            else:
                ev = None
            self._pending.append(_PendingRound(
                round=t, selected=sel_idx, metrics=metrics, eval_pair=ev,
                dyn=dyn_row, defense=defense or None))

    def _flush_pending(self) -> None:
        """Drain the pending buffer with ONE batched device_get and turn
        every entry into a RoundLog (deferring the fetch cannot change
        the values — they were computed by the same programs)."""
        if not self._pending:
            return
        with obs.span("round/drain", rounds=len(self._pending),
                      first=self._pending[0].round):
            fetched = obs.device_get(
                [(p.metrics, p.eval_pair, p.defense)
                 for p in self._pending])
        # watchdog: first divergence trigger across the drained evals (at
        # most ONE rollback per flush — later evals in the same drain ran
        # against the already-poisoned params)
        wd_trigger: Optional[Tuple[str, int]] = None
        wd_healthy_seen = False
        for p, (m, ev, defs) in zip(self._pending, fetched):
            skipped = ev is None
            acc, loss = ((float(ev[0]), float(ev[1])) if not skipped
                         else (float("nan"), float("nan")))
            if not skipped:
                self._last_eval = (acc, loss)
                if not (np.isfinite(acc) and np.isfinite(loss)):
                    # the eval RAN and came back non-finite: the model
                    # diverged (e.g. an unscreened NaN update) — distinct
                    # from an off-cadence skip, and loud in the log
                    obs.OBS.counter("round/diverged")
                    obs.OBS.event("defense", name="round/diverged",
                                  round=p.round)
                if self._watchdog and wd_trigger is None:
                    reason = self._wd_detect(acc, loss)
                    if reason is not None:
                        wd_trigger = (reason, p.round)
                    else:
                        wd_healthy_seen = True
                        self._wd_healthy = True
            self.total_client_reward += float(m["client_reward_sum"])
            self.logs.append(RoundLog(
                round=p.round, selected=p.selected, test_acc=acc,
                test_loss=loss, energy_std=float(m["energy_std"]),
                mean_bid=float(m["mean_bid"]),
                server_reward=float(m["server_reward"]),
                client_reward_sum=float(m["client_reward_sum"]),
                vds_gap=float(m["vds_gap"]), eval_skipped=skipped))
            # per-round series row: every scalar is already a host float
            # from the batched fetch above — recording adds no sync
            extra: Dict[str, float] = {}
            for k in (_DYN_METRIC_KEYS + _DEF_METRIC_KEYS
                      + _SCHEME_METRIC_KEYS):
                if k in m:
                    extra[k] = float(m[k])
            if p.dyn is not None:
                extra.update({k: float(v) for k, v in p.dyn.items()})
            if "num_banned" in extra:
                self.defense_totals["banned_final"] = int(
                    extra["num_banned"])
            if defs:
                nq = sum(float(d["num_quarantined"]) for d in defs)
                ns = sum(float(d["num_screened"]) for d in defs)
                self.defense_totals["quarantined"] += int(nq)
                self.defense_totals["screened"] += int(ns)
                main = defs[-1]     # the synchronous cohort's report
                extra.update(
                    num_quarantined=nq,
                    num_screened=ns,
                    num_survivors=float(main["num_survivors"]),
                    survivor_frac=float(main["survivor_frac"]),
                    clipped_frac=float(main["clipped_frac"]),
                    update_norm_p50=float(main["update_norm_p50"]),
                    update_norm_p99=float(main["update_norm_p99"]),
                    defense_pressure=float(main["defense_pressure"]))
                if nq > 0:
                    obs.OBS.counter("defense/quarantined", int(nq))
                    obs.OBS.event("defense", name="quarantine",
                                  round=p.round, quarantined=int(nq))
                if ns > 0:
                    obs.OBS.counter("defense/screened", int(ns))
                    obs.OBS.event("defense", name="band_screen",
                                  round=p.round, screened=int(ns))
            obs.OBS.record_round(
                p.round, test_acc=acc, test_loss=loss,
                energy_std=float(m["energy_std"]),
                mean_bid=float(m["mean_bid"]),
                server_reward=float(m["server_reward"]),
                client_reward_sum=float(m["client_reward_sum"]),
                vds_gap=float(m["vds_gap"]),
                num_selected=int(p.selected.size),
                eval_skipped=skipped, **extra)
        self._pending.clear()
        if self._watchdog:
            if wd_trigger is not None:
                self._wd_rollback(*wd_trigger)
            elif wd_healthy_seen:
                # the newest drained eval vouches for the CURRENT params:
                # snapshot at this healthy boundary
                self._wd_snapshot(self.logs[-1].round if self.logs else 0)
        obs.flush()        # the logging boundary: sinks see I/O only here

    # -- divergence watchdog -------------------------------------------
    def _wd_detect(self, acc: float, loss: float) -> Optional[str]:
        """Classify one drained eval: None = healthy (detector state
        advances), else the divergence reason.  Loss is judged against a
        slow EMA (spike = watchdog_loss_mult x EMA, with a +0.1 absolute
        slack so near-zero losses don't trip on noise), accuracy against
        its running peak."""
        cfg = self.cfg
        if not (np.isfinite(acc) and np.isfinite(loss)):
            return "non_finite_eval"
        if (self._wd_loss_ema is not None
                and loss > cfg.watchdog_loss_mult * self._wd_loss_ema + 0.1):
            return "loss_spike"
        if acc < self._wd_acc_peak - cfg.watchdog_acc_drop:
            return "acc_collapse"
        self._wd_loss_ema = (loss if self._wd_loss_ema is None
                             else 0.5 * self._wd_loss_ema + 0.5 * loss)
        self._wd_acc_peak = max(self._wd_acc_peak, acc)
        return None

    def _wd_snapshot(self, t: int) -> None:
        """Push the current server state onto the checkpoint ring: the
        tree refs are immutable device arrays, so this is O(host mirrors)
        — no device round-trip, no disk."""
        self._wd_ring.append(_RingEntry(
            round=t, tree=self._ckpt_tree(),
            reward=self.total_client_reward,
            last_eval=self._last_eval,
            dyn_rng_state=(deepcopy(self._dyn_rng.bit_generator.state)
                           if self.dynamics else None),
            host_avail=(self._host_avail.copy() if self.dynamics
                        else None)))
        self.watchdog_totals["snapshots"] += 1

    def _wd_rollback(self, reason: str, bad_round: int) -> None:
        """Restore the newest healthy ring entry, tighten the defense,
        decay the server LR and perturb the key chain so the retried
        rounds explore a different stochastic path.  If the previous
        rollback never produced a healthy eval, the newest entry itself
        is suspect (snapshotted ahead of its validating eval) — it is
        discarded and the next-older entry restores instead."""
        cfg = self.cfg
        if not self._wd_ring:
            return
        if not self._wd_healthy and len(self._wd_ring) > 1:
            self._wd_ring.pop()
        e = self._wd_ring[-1]
        tree = e.tree
        self._wd_rollbacks += 1
        self.params = tree["params"]
        self.state = tree["state"]
        # perturbed key chain: replaying the exact keys would walk the
        # exact same path back into the divergence
        self.key = jax.random.fold_in(
            tree["key"], np.uint32(0x5AFE + self._wd_rollbacks))
        self._host_history = np.asarray(tree["host_history"],
                                        np.int64).copy()
        if self.dynamics:
            self.dyn_state = DYN.DynamicsState(avail=tree["dyn_avail"])
            self._dyn_key = tree["dyn_key"]
            self._host_avail = e.host_avail.copy()
            self._dyn_rng.bit_generator.state = deepcopy(e.dyn_rng_state)
            # in-flight late updates were trained from abandoned params
            self._late_buffer = []
        if self.defended:
            # escalate from the CURRENT tighten, not the snapshot's: a
            # second rollback onto the same restore point retries with a
            # tighter band than the first, not an identical one
            ds = tree["defense_state"]
            if ds.tighten is not None:
                ds = dc_replace(ds, tighten=self._defense_state.tighten
                                * jnp.float32(cfg.watchdog_tighten))
            self._defense_state = ds
        self._srv_lr = self._srv_lr * jnp.float32(cfg.watchdog_lr_decay)
        self.total_client_reward = e.reward
        self._last_eval = e.last_eval
        self._wd_loss_ema = None
        self._wd_acc_peak = float("-inf")
        self._wd_healthy = False
        self.watchdog_totals["rollbacks"] = self._wd_rollbacks
        obs.OBS.counter("watchdog/rollbacks")
        obs.OBS.event("watchdog", name="rollback", round=bad_round,
                      restored_round=e.round, reason=reason,
                      rollbacks=self._wd_rollbacks)

    # -- crash tolerance -----------------------------------------------
    def _ckpt_tree(self) -> Dict[str, Any]:
        """Everything array-valued the round loop's future depends on.
        The in-flight FedBuff late buffer is deliberately NOT saved: a
        crash loses updates that never folded into the model, which is
        exactly FedBuff's semantics for a server restart."""
        tree: Dict[str, Any] = {
            "params": self.params, "state": self.state, "key": self.key,
            # int32: restore round-trips leaves through jnp, which would
            # silently narrow int64 under default (x64-off) jax config
            "host_history": self._host_history.astype(np.int32)}
        if self.dynamics:
            tree["dyn_avail"] = self.dyn_state.avail
            tree["dyn_key"] = self._dyn_key
        if self.defended:
            tree["defense_state"] = self._defense_state
        if self._watchdog:
            tree["server_lr"] = self._srv_lr
        return tree

    def save_checkpoint(self, path: str, step: int) -> None:
        """Persist server params + selection/dynamics/defense state so a
        crashed run resumes from the last boundary (repro.checkpoint.io);
        host-side rng state and reward tally ride the json manifest."""
        from repro.checkpoint import io as CKPT
        extra: Dict[str, Any] = {
            "total_client_reward": self.total_client_reward,
            # the active selection scheme rides the manifest so a resume
            # under a different --scheme-select fails loudly instead of
            # silently diverging (the restored scheme_state pytree and
            # the key-consumption pattern are both scheme-shaped)
            "scheme_select": self.cfg.scheme_select}
        if self._watchdog:
            extra["watchdog_rollbacks"] = self._wd_rollbacks
        if self.dynamics:
            # the replacement sampler's host rng state is json-friendly
            # (PCG64 state dict of ints) — resumed draws continue the
            # exact chain a continuous run would have used
            extra["dyn_rng_state"] = self._dyn_rng.bit_generator.state
        with obs.span("run/checkpoint", step=step):
            CKPT.save(path, self._ckpt_tree(), step=step, extra=extra)

    def load_checkpoint(self, path: str) -> int:
        """Restore a :meth:`save_checkpoint` snapshot and return the next
        round index.  Stage-1 clustering must NOT be re-run afterwards:
        the restored key already reflects its chain consumption and the
        cluster ids live in the restored SelectionState.

        Raises ValueError when the snapshot's manifest records a
        different selection scheme than this server's
        ``cfg.scheme_select``: the checkpointed scheme_state pytree and
        key chain are scheme-shaped, so continuing under another scheme
        would silently diverge (or crash deep inside restore with a
        structure mismatch) — the manifest is checked FIRST."""
        from repro.checkpoint import io as CKPT
        manifest0 = path.removesuffix(".npz") + ".json"
        if os.path.exists(manifest0):
            with open(manifest0) as f:
                saved = (json.load(f).get("extra") or {}).get(
                    "scheme_select", "paper")
            if saved != self.cfg.scheme_select:
                raise ValueError(
                    f"checkpoint {path!r} was written by selection scheme "
                    f"{saved!r} but this run uses --scheme-select "
                    f"{self.cfg.scheme_select!r}; resume with "
                    f"--scheme-select {saved} or start a fresh run")
        tree, step = CKPT.restore(path, self._ckpt_tree())
        self.params = tree["params"]
        self.state = tree["state"]
        self.key = tree["key"]
        self._host_history = np.asarray(
            obs.device_get(tree["host_history"]), np.int64)
        if self.dynamics:
            self.dyn_state = DYN.DynamicsState(avail=tree["dyn_avail"])
            self._dyn_key = tree["dyn_key"]
            self._host_avail = np.asarray(
                obs.device_get(tree["dyn_avail"]), bool)
            self._host_clusters = np.asarray(
                obs.device_get(self.state.clusters), np.int64)
        if self.defended:
            self._defense_state = tree["defense_state"]
        if self._watchdog:
            self._srv_lr = tree["server_lr"]
        manifest = path.removesuffix(".npz") + ".json"
        if os.path.exists(manifest):
            with open(manifest) as f:
                extra = json.load(f).get("extra") or {}
            self.total_client_reward = float(
                extra.get("total_client_reward", 0.0))
            st = extra.get("dyn_rng_state")
            if self.dynamics and st is not None:
                self._dyn_rng.bit_generator.state = st
            if self._watchdog:
                self._wd_rollbacks = int(
                    extra.get("watchdog_rollbacks", 0))
                self.watchdog_totals["rollbacks"] = self._wd_rollbacks
        return step

    def run_round(self, t: int) -> RoundLog:
        """One synchronous FL round (dispatch + immediate flush) — the
        single-round API; the async pipeline lives in :meth:`run`."""
        self._dispatch_round(t, self._eval_due(t))
        self._flush_pending()
        return self.logs[-1]

    # ------------------------------------------------------------------
    def run(self, rounds: Optional[int] = None, verbose: bool = False,
            audit_sync: bool = False, audit_warm_rounds: int = 2,
            checkpoint_every: int = 0,
            checkpoint_path: Optional[str] = None, resume: bool = False):
        """The async round loop.  ``verbose`` prints a progress line
        every 5 rounds showing the *last drained* eval (NaN until one
        drains) — verbosity must never change the measured eval cadence
        (it used to force an eval at every print boundary, so logs and
        params depended on the flag; regression-tested in
        tests/test_obs.py).  ``audit_sync`` wraps every dispatch from
        round ``audit_warm_rounds`` on in the transfer-guard sync
        auditor: an implicit host transfer inside the warm loop raises
        at the offending op (obs.sync_audit).

        ``checkpoint_every`` > 0 (with a ``checkpoint_path``) snapshots
        params + server state every that many rounds; ``resume`` picks
        the run back up from an existing snapshot — stage-1 clustering
        is skipped because the restored state already carries its result
        (and the restored key its chain consumption), so a resumed
        dynamics-free run walks the remaining rounds bit-identically to
        an uninterrupted one (tests/test_checkpoint.py)."""
        start = 0
        if resume and checkpoint_path is not None and os.path.exists(
                checkpoint_path.removesuffix(".npz") + ".npz"):
            start = self.load_checkpoint(checkpoint_path)
            obs.log(f"resumed checkpoint {checkpoint_path!r} "
                    f"at round {start}")
        if start == 0:
            with obs.span("run/cluster", scheme=self.cfg.scheme):
                self.cluster()
        warmup = getattr(self.runtime, "warmup", None)
        if warmup is not None:    # device runtime: compile every class
            with obs.span("run/warmup"):
                warmup(self.params)
        if self._watchdog and not self._wd_ring:
            # seed the ring with the pre-training state so even a
            # round-0 divergence has a healthy entry to roll back to
            self._wd_snapshot(start - 1)
        T = rounds if rounds is not None else self.cfg.rounds
        for t in range(start, T):
            printing = verbose and (t % 5 == 0 or t == T - 1)
            final = t == T - 1
            eval_now = self._eval_due(t, final=final)
            if audit_sync and t >= audit_warm_rounds:
                with obs.sync_audit():
                    self._dispatch_round(t, eval_now, final=final)
            else:
                self._dispatch_round(t, eval_now, final=final)
            if self._watchdog and eval_now and not printing:
                # the detector lives at flush boundaries: with the
                # watchdog on, every eval round IS a flush boundary so a
                # divergence is caught within one eval cadence
                self._flush_pending()
            if printing:
                self._flush_pending()
                log = self.logs[-1]
                acc, loss = self._last_eval
                obs.log(f"  round {t:3d} acc={acc:.3f} "
                        f"loss={loss:.3f} "
                        f"E_std={log.energy_std:.3f} "
                        f"bid={log.mean_bid:.3f} "
                        f"vds_gap={log.vds_gap:.3f}")
            if (checkpoint_every > 0 and checkpoint_path is not None
                    and (t + 1) % checkpoint_every == 0 and not final):
                # flush first so the log stream is consistent up to the
                # snapshot boundary a resumed run continues from
                self._flush_pending()
                self.save_checkpoint(checkpoint_path, t + 1)
        self._flush_pending()
        return self.logs
