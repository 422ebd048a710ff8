"""CohortRuntime: the execution backends for a round's local training
(selected via ``FLConfig.runtime`` / ``train.py --runtime``).

  * ``sequential`` — the reference oracle: one jitted local step, Python
    loops over clients and minibatches (the paper's own execution model).
  * ``device`` — the device-resident fleet pipeline (repro.sim.fleet):
    all clients' data is packed once at init into per-capacity-class
    device tensors; per-round cohort assembly is an on-device gather by
    winner rows driven by tiny host-built int plans, and the compiled
    programs are keyed on *static* fleet-derived capacity classes so
    nothing retraces after warm-up.  On a multi-device host the
    per-invocation client axis is shard_map'd over the cohort mesh's
    ``data`` axis with a psum FedAvg.

Both backends are bit-compatible in *behavior* (same shuffles, same
batch boundaries, same FedAvg weights); results agree up to float
reassociation.  The sequential backend stays the ground truth the device
backend is tested against (tests/test_sim.py).
"""
from __future__ import annotations

from typing import Any, List, Optional, Protocol

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import FLConfig
from repro.core.adapters import ModelAdapter
from repro.core.aggregation import UpdateBatch, make_flat_delta
from repro.optim import apply_updates, fedprox_grad, sgd
from repro.sim.cohort import (HostPlanCache, drop_zero_size_winners,
                              pack_feature_pass)
from repro.sim.engine import CohortEngine
from repro.sim.fleet import FleetStore, class_work

RUNTIMES = ("sequential", "device")


def tree_weighted_sum(trees: List[Any], weights: np.ndarray):
    """sum_k p_k * tree_k (the FedAvg reduction)."""
    out = jax.tree.map(lambda x: x * weights[0], trees[0])
    for t, w in zip(trees[1:], weights[1:]):
        out = jax.tree.map(lambda a, b: a + b * w, out, t)
    return out


class CohortRuntime(Protocol):
    """What FederatedServer needs from an execution backend."""

    name: str

    def train_cohort(self, global_params, sel_idx: np.ndarray,
                     history: np.ndarray) -> Optional[Any]:
        """Run local training for the winners and return the aggregated
        global params (None for an empty cohort). ``history`` is a HOST
        array (the server's participation mirror) — per-winner shuffle
        seeds index it directly, so the control plane never pays a
        per-client device sync for rng seeding."""
        ...

    def train_client(self, global_params, client_idx: int,
                     history_count: int) -> Any:
        """One client's local params after its local epochs."""
        ...

    def train_cohort_updates(self, global_params, sel_idx: np.ndarray,
                             history: np.ndarray):
        """Defended-path stage-3: the same local training, but instead
        of the fused FedAvg aggregate return the cohort's per-client
        flat param deltas as an UpdateBatch (repro.core.aggregation) —
        (C, D) deltas + weights + client ids, padding rows all-zero with
        id -1 — for the server's screened aggregation.  None for an
        empty cohort."""
        ...

    def cluster_features(self, global_params, key,
                         feature_kind: str) -> Optional[jnp.ndarray]:
        """(N, D) *raw* clustering features, or None to use the reference
        per-client loop in repro.core.clustering. Either way the blocked
        JL projection and the jitted k-means engine run downstream in
        clustering.cluster_clients, so both runtimes share one code path
        from raw features onward."""
        ...


# ----------------------------------------------------------------------
class SequentialRuntime:
    """Reference oracle: the seed implementation's per-client loop."""

    name = "sequential"

    def __init__(self, cfg: FLConfig, adapter: ModelAdapter,
                 x: np.ndarray, y: np.ndarray, clients):
        self.cfg = cfg
        self.adapter = adapter
        self.x, self.y = x, y
        self.clients = clients
        self._local_step = jax.jit(self._make_local_step())

    def _make_local_step(self):
        _, upd = sgd(self.cfg.lr, momentum=self.cfg.local_momentum)

        def step(params, opt_state, batch, global_params):
            g = self.adapter.grad(params, batch)
            if self.cfg.aggregator == "fedprox":
                g = fedprox_grad(g, params, global_params,
                                 self.cfg.fedprox_mu)
            u, opt_state = upd(g, opt_state, params)
            return apply_updates(params, u), opt_state

        return step

    def train_client(self, global_params, client_idx: int,
                     history_count: int):
        cfg = self.cfg
        c = self.clients[client_idx]
        x, y = self.x[c.train_idx], self.y[c.train_idx]
        init, _ = sgd(cfg.lr, momentum=cfg.local_momentum)
        p = global_params
        opt = init(p)
        bs = min(32, len(x))
        rng = np.random.default_rng(int(history_count) * 977 + client_idx)
        for _ in range(cfg.local_epochs):
            order = rng.permutation(len(x))
            for i in range(0, len(x) - bs + 1, bs):
                idx = order[i:i + bs]
                p, opt = self._local_step(
                    p, opt, {"x": x[idx], "y": y[idx]}, global_params)
        return p

    def train_cohort(self, global_params, sel_idx, history):
        history = np.asarray(history)       # host mirror; never a jnp sync
        # drop zero-size winners: they have no minibatches to run and no
        # FedAvg mass — with ALL sizes zero the old ``pk = sizes`` path
        # silently multiplied the global params by an all-zero weight
        # vector (tree_weighted_sum -> zero params)
        sel_idx = drop_zero_size_winners(sel_idx, self.clients)
        if sel_idx.size == 0:
            return None
        with obs.span("cohort/train", runtime=self.name,
                      cohort=int(sel_idx.size)):
            locals_ = [self.train_client(global_params, int(i),
                                         int(history[int(i)]))
                       for i in sel_idx]
            sizes = np.array([self.clients[int(i)].size for i in sel_idx],
                             np.float64)
            pk = sizes / sizes.sum()
            return tree_weighted_sum(locals_, pk)

    def train_cohort_updates(self, global_params, sel_idx, history):
        history = np.asarray(history)
        sel_idx = drop_zero_size_winners(sel_idx, self.clients)
        if sel_idx.size == 0:
            return None
        if getattr(self, "_flat_delta", None) is None:
            self._flat_delta = make_flat_delta(global_params)
        with obs.span("cohort/train", runtime=self.name,
                      cohort=int(sel_idx.size), defended=True):
            rows = [self._flat_delta(
                        self.train_client(global_params, int(i),
                                          int(history[int(i)])),
                        global_params)
                    for i in sel_idx]
            sizes = np.array([self.clients[int(i)].size for i in sel_idx],
                             np.float64)
            pk = sizes / sizes.sum()
            return UpdateBatch(deltas=jnp.stack(rows),
                               weights=pk.astype(np.float32),
                               client_idx=np.asarray(sel_idx, np.int32))

    def cluster_features(self, global_params, key, feature_kind):
        return None   # use the reference loop in clustering.cluster_clients


# ----------------------------------------------------------------------
class DeviceRuntime(SequentialRuntime):
    """Device-resident fleet backend (repro.sim.fleet): the whole fleet's
    data lives on device in static capacity-class tensors; per-round host
    work shrinks to assembling tiny int index plans (winner rows + the
    oracle's shuffle permutations), and every compiled program is keyed
    on a fleet-derived class shape, so nothing retraces after
    :meth:`warmup`.  On a multi-device host the per-invocation client
    axis is shard_map'd over the cohort mesh's ``data`` axis (replicated
    store, psum FedAvg).

    Inherits the oracle's ``train_client`` (single-client calls have no
    batching to exploit).  Its stage-1 feature passes run on one device
    as engine programs over host-gathered tensors: they feed stage-1
    clustering, whose labels must stay bit-identical to the reference's.
    """

    name = "device"

    def __init__(self, cfg, adapter, x, y, clients, mesh=None):
        super().__init__(cfg, adapter, x, y, clients)
        if mesh is None:
            from repro.launch.mesh import make_cohort_mesh
            m = make_cohort_mesh(cfg.cohort_mesh_devices)
            # the 1-device debug mesh would only add shard_map overhead
            mesh = m if m.shape["data"] > 1 else None
        self.engine = CohortEngine(adapter, cfg, mesh=mesh)
        # memoized plan structure + per-client local data shards, shared
        # by the fleet store and the weight feature pass
        self.plan_cache = HostPlanCache(x, y, clients, cfg.local_epochs)
        self.store = FleetStore(x, y, clients, cfg,
                                client_multiple=self.engine.data_axis_size,
                                cache=self.plan_cache)
        # the class tensors now hold the fleet on device — don't keep a
        # host duplicate of the whole pool alive for the rest of the run
        # (a feature pass lazily re-gathers what it needs, once)
        self.plan_cache.drop_local_data()
        self._warmed = False

    def warmup(self, global_params):
        """Compile every capacity class's program up front (one fully
        masked invocation per (class, tier)) so the round loop never
        traces.  Idempotent: re-running (e.g. a second ``run()`` call)
        would re-dispatch real masked scans against a hot jit cache."""
        if self._warmed:
            return
        batches = self.store.warmup_batches()
        with obs.span("fleet/warmup", classes=len(self.store.classes),
                      programs=len(batches)):
            for b in batches:
                c = self.store.classes[b.cls_id]
                staged = self._put_batch(b, c)
                if self.cfg.defended:
                    # defended rounds call the per-row updates program
                    # instead of the fused one — warm that variant so the
                    # screened path keeps the zero-warm-retrace guarantee
                    jax.block_until_ready(self.engine.train_class_updates(
                        global_params, *staged[:6], feat=c.feat))
                else:
                    jax.block_until_ready(self.engine.train_class(
                        global_params, *staged, feat=c.feat))
        obs.jax_stats.note_work("fleet", programs=len(batches))
        self._warmed = True

    def _put_batch(self, b, c):
        """Stage one class batch's host-built plan arrays on device via
        the *counted explicit* transfer wrapper.  These tiny int plans are
        the round loop's only intended h2d traffic; routing them through
        obs.device_put is what makes the warm loop pass the sync auditor
        (implicit numpy->jit transfers are disallowed there) and keeps
        the byte accounting honest."""
        with obs.span("cohort/put"):
            rows, plans, mask, n, w = obs.device_put(
                (b.rows, b.plans, b.step_mask, b.batch_n, b.weights))
        return c.x, c.y, rows, plans, mask, n, w

    def _assemble(self, sel_idx, history, sharded: bool):
        """The round's class batches.  While obs records, adds what
        their calls will run to ``obs.jax_stats`` (``stage3/``, one
        ``assemblies`` a call), after the ``cohort/assemble`` span so
        that the span times the plan alone."""
        with obs.span("cohort/assemble",
                      winners=int(np.asarray(sel_idx).size)):
            batches = self.store.assemble(sel_idx, np.asarray(history))
        if obs.OBS.recording:
            obs.jax_stats.note_work("stage3", assemblies=1, **class_work(
                batches,
                lambda rows: self.engine.client_chunks(rows, sharded)))
        return batches

    def train_cohort(self, global_params, sel_idx, history):
        batches = self._assemble(sel_idx, history, sharded=True)
        with obs.span("cohort/train", runtime=self.name,
                      classes=len(batches)):
            agg = None
            for b in batches:
                c = self.store.classes[b.cls_id]
                part = self.engine.train_class(global_params,
                                               *self._put_batch(b, c),
                                               feat=c.feat)
                agg = part if agg is None else jax.tree.map(jnp.add, agg,
                                                            part)
            return agg

    def train_cohort_updates(self, global_params, sel_idx, history):
        # the updates program is always the single-device one
        batches = self._assemble(sel_idx, history, sharded=False)
        if not batches:
            return None
        with obs.span("cohort/train", runtime=self.name,
                      classes=len(batches), defended=True):
            parts, ws, ids = [], [], []
            for b in batches:
                c = self.store.classes[b.cls_id]
                parts.append(self.engine.train_class_updates(
                    global_params, *self._put_batch(b, c)[:6],
                    feat=c.feat))
                ws.append(np.asarray(b.weights, np.float32))
                ids.append(np.asarray(b.client_idx, np.int32))
            # padding rows ride along (all-zero delta, id -1, weight 0);
            # the server compacts them out before the screened program
            return UpdateBatch(deltas=jnp.concatenate(parts, axis=0),
                               weights=np.concatenate(ws),
                               client_idx=np.concatenate(ids))

    def cluster_features(self, global_params, key, feature_kind):
        with obs.span("cluster/features", feature=feature_kind,
                      runtime=self.name):
            if feature_kind == "weights":
                # the cache's epochs field is unused by the feature plan
                # (one in-order epoch); sharing it reuses the local data
                # gathers
                buckets = pack_feature_pass(
                    self.x, self.y, self.clients,
                    chunk_width=self.cfg.cohort_vmap_width,
                    cache=self.plan_cache)
                return self.engine.weight_features(global_params, buckets,
                                                   len(self.clients))
            return self.engine.gradient_features(
                global_params, *self._gather_gradient_windows(key))

    def _gather_gradient_windows(self, key):
        """Reproduce the reference feature pass's sample-window draws
        (same fold_in stream as clustering.cluster_clients) and gather
        them into uniform (N, T0, window, ...) tensors."""
        from repro.core.clustering import window_indices
        cfg = self.cfg
        t0, w = cfg.cluster_resamples, cfg.sample_window
        n = len(self.clients)
        xb = np.empty((n, t0, w) + self.x.shape[1:], self.x.dtype)
        yb = np.empty((n, t0, w), self.y.dtype)
        for i, c in enumerate(self.clients):
            shard = np.asarray(c.train_idx)
            ki = jax.random.fold_in(key, i)
            for t in range(t0):
                k = jax.random.fold_in(ki, t)
                idx = np.asarray(window_indices(k, len(shard), w))
                g = shard[idx]
                xb[i, t] = self.x[g]
                yb[i, t] = self.y[g]
        return xb, yb


# ----------------------------------------------------------------------
def make_runtime(cfg: FLConfig, adapter: ModelAdapter, x, y,
                 clients) -> CohortRuntime:
    if cfg.runtime == "sequential":
        return SequentialRuntime(cfg, adapter, x, y, clients)
    if cfg.runtime == "device":
        return DeviceRuntime(cfg, adapter, x, y, clients)
    raise ValueError(
        f"unknown FLConfig.runtime={cfg.runtime!r}; expected {RUNTIMES}")
