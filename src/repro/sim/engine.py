"""Vectorized cohort engine: one compiled program per bucket shape.

Three compiled entry points, all ``jax.vmap`` over the client axis with a
``jax.lax.scan`` over minibatch steps inside:

When constructed with a ``mesh`` (the sharded runtime), the round-training
entry point additionally maps each bucket's client axis across the mesh's
``data`` axis with ``shard_map``: params are replicated in, every device
runs the same chunked vmap/scan program over its C/ndev slice of the
bucket tensors, and the weighted FedAvg partial sum is reduced on-mesh
with a ``psum`` — so the per-round result comes back replicated and only
the reduction order differs from the single-device program (same
float-reassociation tolerance class as vectorized-vs-sequential).  The
packer pads the client axis to a multiple of the data-axis size, so the
shard split is always even; feature passes stay on the single-device path
(they feed stage-1 clustering, whose logs must be bit-identical across
runtimes).

  * :meth:`CohortEngine.train_bucket` — the round's local training: every
    client runs ``local_epochs`` of SGD (optionally FedProx-proximal)
    from the shared global params; masked (padding) steps are the
    identity on both params and optimizer state; the bucket's weighted
    FedAvg partial sum is fused into the same program.
  * :meth:`CohortEngine.weight_features` — the Wang-et-al clustering
    feature: flattened param delta after one in-order epoch of plain SGD.
  * :meth:`CohortEngine.gradient_features` — the paper's clustering
    feature: mean flattened gradient over the T0 sample-window draws.
  * :meth:`CohortEngine.train_class` — the device-resident twin of
    ``train_bucket`` for the ``device`` runtime (repro.sim.fleet): instead
    of consuming host-packed ``(C, S, bs, ...)`` minibatch tensors it
    takes a capacity class's resident ``(P, n_rows, width)`` store of
    flat sample rows plus tiny per-round int tensors (winner rows + local
    batch plans) and gathers each step's minibatch *inside* the compiled
    program (``jnp.take`` by winner row, then a per-step take over the
    plan, cut to ``prod(feat)`` values and reshaped to ``(bs, *feat)``
    for the model; both takes clip, exact for the in-range indices
    ``FleetStore.assemble`` writes), so
    per-round host work is index assembly only — no sample ever crosses
    host->device after init.  Capacity classes are static (derived from
    the whole fleet at init), so these programs compile once per class;
    ``obs.jax_stats`` counts traces (``traces/cohort_engine``) and
    per-shape cache hits/misses to make "zero retraces after warm-up"
    assertable.

``jax.jit`` retraces per distinct bucket shape ``(C, S, bs)``; the packer
pads C to a multiple of the vmap chunk width, S to a multiple of 4, and
band-buckets step counts by power of two to keep that cache small.  The client axis is processed in ``cfg.cohort_vmap_width``-wide
vmap chunks under an outer ``jax.lax.map``: a full-width vmap multiplies
the per-op working set by C and thrashes the CPU cache (measured 1.4-2x
slower than the loop for the paper's CNNs), while narrow chunks keep
each op cache-resident and still amortize dispatch to one call per
bucket.  Equivalence with the sequential oracle is exact up to float
reassociation (tested in tests/test_sim.py).
"""
from __future__ import annotations

import math
from typing import Any, List, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import FLConfig
from repro.core.adapters import ModelAdapter
from repro.optim import apply_updates, fedprox_grad, sgd
from repro.sim.cohort import CohortBucket


def _flatten_tree(tree) -> jnp.ndarray:
    return jnp.concatenate([x.reshape(-1) for x in jax.tree.leaves(tree)])


def _chunk_width(c: int, width: int) -> int:
    """Largest power of two <= width that divides c."""
    w = 1
    while w * 2 <= min(width, c) and c % (w * 2) == 0:
        w *= 2
    return w


def _varying(tree):
    """Mark a replicated tree as varying over the ``data`` axis inside a
    ``shard_map`` body: the local-SGD scan carry (params and optimizer
    state) starts replicated and comes back per-shard, and a scan's carry
    must keep one type."""
    return jax.tree.map(
        lambda a: jax.lax.pcast(a, ("data",), to="varying"), tree)


def _same(tree):
    return tree


def _client_map(fn, args: Tuple[jnp.ndarray, ...], width: int):
    """Map ``fn`` over the leading client axis of every array in ``args``:
    vmap in ``width``-wide chunks under an outer ``lax.map`` (see module
    docstring for why not one full-width vmap)."""
    c = args[0].shape[0]
    w = _chunk_width(c, width)
    if w == c:
        return jax.vmap(fn)(*args)
    re = tuple(a.reshape((c // w, w) + a.shape[1:]) for a in args)
    chunks = jax.lax.map(lambda ch: jax.vmap(fn)(*ch), re)
    return jax.tree.map(lambda a: a.reshape((c,) + a.shape[2:]), chunks)


class CohortEngine:
    def __init__(self, adapter: ModelAdapter, cfg: FLConfig, mesh=None):
        self.adapter = adapter
        self.cfg = cfg
        self.mesh = mesh
        # per-call shape signatures seen, for obs.jax_stats' hit/miss
        # counters
        self._seen_shapes = set()
        self._train = self._build_train()      # jitted inside the builder
        self._train_sharded = (self._build_train_sharded()
                               if mesh is not None else None)
        self._train_gather = self._build_train_gather()
        self._train_gather_sharded = (self._build_train_gather_sharded()
                                      if mesh is not None else None)
        # per-client flat-delta twins for the defended aggregation path
        # (repro.core.aggregation): built lazily — defense-off runs never
        # construct them, so their jit caches can't perturb anything
        self._train_updates = None
        self._train_gather_updates = None
        self._weight_feats = jax.jit(self._build_weight_features())
        self._grad_feats = jax.jit(self._build_gradient_features())

    @property
    def data_axis_size(self) -> int:
        """Client-axis shard count (1 when unsharded)."""
        return 1 if self.mesh is None else self.mesh.shape["data"]

    def client_chunks(self, rows: int, sharded: bool) -> int:
        """Client chunks that a round-training program over ``rows``
        clients runs one after another on each device: the ``lax.map``
        of :func:`_client_map` over the rows a device holds (all of
        them, or a ``data``-axis share for the mesh-mapped program)."""
        local = rows // (self.data_axis_size if sharded else 1)
        return local // _chunk_width(local, self.cfg.cohort_vmap_width)

    def _note_shape(self, key) -> None:
        obs.jax_stats.note_shape(key in self._seen_shapes)
        self._seen_shapes.add(key)

    # ------------------------------------------------------------------
    def _masked_step(self, grad, opt_update, proximal: bool, global_params):
        """One masked local SGD step shared by both scan flavors: a
        masked (padding) step is the identity on params AND opt state.
        ``grad(p, batch)`` is the step's gradient."""

        def apply(p, opt, xs, ys, m):
            g = grad(p, {"x": xs, "y": ys})
            if proximal:
                g = fedprox_grad(g, p, global_params, self.cfg.fedprox_mu)
            u, opt2 = opt_update(g, opt, p)
            p2 = apply_updates(p, u)
            keep = m > 0.5
            return jax.tree.map(lambda a, b: jnp.where(keep, b, a),
                                (p, opt), (p2, opt2))

        return apply

    def _local_scan(self, params0, opt_init, opt_update, xb, yb, mask,
                    global_params, proximal: bool, vary=_same):
        """Scan ``local_step`` over the step axis for one client.
        ``vary`` maps the initial carry (``_varying`` inside shard_map)."""
        upd = self._masked_step(self.adapter.grad, opt_update, proximal,
                                global_params)

        def step(carry, inp):
            xs, ys, m = inp
            return upd(*carry, xs, ys, m), None

        (p, _), _ = jax.lax.scan(step, vary((params0, opt_init(params0))),
                                 (xb, yb, mask))
        return p

    def _local_scan_gather(self, params0, opt_init, opt_update, x_row,
                           y_row, plan, mask, n, feat, global_params,
                           proximal: bool, vary=_same):
        """The device-resident twin of :meth:`_local_scan`: the scan
        carries the client's resident (n_rows, width) sample rows and
        gathers each step's (bs,) minibatch by plan indices, cut to its
        first prod(feat) values and reshaped to (bs, *feat) — the padded
        (S, bs, *feat) tensor of the host-packed path is never
        materialized.  ``n``: the client's real samples per step, the
        first ``n`` of the batch (``ModelAdapter.masked_grad``).  Plan
        indices are in range, so the takes clip (no fill select)."""
        upd = self._masked_step(
            lambda p, batch: self.adapter.masked_grad(p, batch, n),
            opt_update, proximal, global_params)

        def step(carry, inp):
            idx, m = inp
            xs = jnp.take(x_row, idx, axis=0, mode="clip")[
                ..., :math.prod(feat)].reshape(idx.shape + feat)
            ys = jnp.take(y_row, idx, axis=0, mode="clip")
            return upd(*carry, xs, ys, m), None

        (p, _), _ = jax.lax.scan(step, vary((params0, opt_init(params0))),
                                 (plan, mask))
        return p

    def _build_train_core(self, vary=_same):
        """Shared round-training body used by both the single-device and
        the mesh-mapped builders: per-client local scans (chunked vmap)
        plus the f32 weighted FedAvg partial.  Returns (stacked, partial)
        — callers finish the reduction (astype, or psum + astype)."""
        cfg = self.cfg
        init, upd = sgd(cfg.lr, momentum=cfg.local_momentum)
        proximal = cfg.aggregator == "fedprox"

        def core(global_params, xb, yb, mask, weights):
            obs.jax_stats.note_trace("cohort_engine")

            def one_client(cx, cy, cm):
                return self._local_scan(global_params, init, upd, cx, cy,
                                        cm, global_params, proximal, vary)

            stacked = _client_map(one_client, (xb, yb, mask),
                                  cfg.cohort_vmap_width)
            partial = jax.tree.map(
                lambda leaf: jnp.tensordot(weights,
                                           leaf.astype(jnp.float32),
                                           axes=1),
                stacked)
            return stacked, partial

        return core

    def _build_train(self):
        core = self._build_train_core()

        def train(global_params, xb, yb, mask, weights,
                  return_stacked=False):
            stacked, partial = core(global_params, xb, yb, mask, weights)
            agg = jax.tree.map(lambda p, s: p.astype(s.dtype),
                               partial, stacked)
            # only materialize the (C, ...) per-client trees as a jit
            # output when asked — the round loop needs just the aggregate
            # (XLA drops the unfetched stacked outputs otherwise)
            return (stacked, agg) if return_stacked else agg

        return jax.jit(train, static_argnames="return_stacked")

    def _flat_deltas(self, stacked, global_params) -> jnp.ndarray:
        """(C, D) float32 flat param deltas from a stacked (leading-C)
        per-client tree — leaf/concat order is jax.tree.leaves, matching
        repro.core.aggregation's flatten/apply helpers."""
        flats = jax.tree.map(
            lambda s, g: (s.astype(jnp.float32) - g[None].astype(
                jnp.float32)).reshape(s.shape[0], -1),
            stacked, global_params)
        return jnp.concatenate(jax.tree.leaves(flats), axis=1)

    def _build_train_updates(self):
        """Per-client flat-delta twin of ``_build_train`` for the
        defended aggregation path: same local scans, but instead of the
        fused FedAvg partial it returns the (C, D) update matrix the
        screened aggregation consumes.  Single-device only — the
        defended path's screening program is a single-device reduction
        anyway (see DESIGN.md §Threat model)."""
        core = self._build_train_core()

        def train(global_params, xb, yb, mask):
            stacked, _ = core(global_params, xb, yb, mask,
                              jnp.zeros((xb.shape[0],), jnp.float32))
            return self._flat_deltas(stacked, global_params)

        return jax.jit(train)

    def _build_train_sharded(self):
        """The mesh-mapped twin of ``_build_train``: shard_map over the
        'data' axis, per-device chunked vmap/scan, FedAvg partial reduced
        with an on-mesh psum.  Only the aggregate is returned (the stacked
        per-client trees would live sharded on-device; the inspection path
        stays on the single-device program)."""
        from repro.sharding.rules import (cohort_bucket_specs,
                                          cohort_param_spec)
        core = self._build_train_core(vary=_varying)

        def shard_body(global_params, xb, yb, mask, weights):
            stacked, partial = core(global_params, xb, yb, mask, weights)
            # psum the per-device partial across 'data' — weights are
            # global (they sum to 1 over ALL shards of ALL buckets), so
            # shard partials just add, same as bucket partials
            return jax.tree.map(
                lambda p, s: jax.lax.psum(p, "data").astype(s.dtype),
                partial, stacked)

        train = jax.shard_map(
            shard_body, mesh=self.mesh,
            in_specs=(cohort_param_spec(),) + cohort_bucket_specs(),
            out_specs=cohort_param_spec())
        return jax.jit(train)

    def _build_train_gather_core(self, vary=_same):
        """Round-training body for the device-resident fleet path: take
        the winners' rows out of the class store (flat sample rows;
        ``feat`` is a sample's shape), run the same chunked vmap/scan as
        the bucket path with per-step index gathers, and fuse the f32
        weighted FedAvg partial.  ``batch_n (C,)`` holds each row's real
        samples per step.  Winner rows are in range, so the takes clip: no call
        reads the store beyond the rows it gathers.  Returns (stacked,
        partial) — callers pick one (XLA drops the unfetched output) and
        finish the reduction (astype, or psum + astype)."""
        cfg = self.cfg
        init, upd = sgd(cfg.lr, momentum=cfg.local_momentum)
        proximal = cfg.aggregator == "fedprox"

        def core(global_params, class_x, class_y, rows, plans, mask,
                 batch_n, weights, feat):
            obs.jax_stats.note_trace("cohort_engine")
            # (C, n_rows, width)
            xg = jnp.take(class_x, rows, axis=0, mode="clip")
            yg = jnp.take(class_y, rows, axis=0, mode="clip")

            def one_client(x_row, y_row, plan, m, n):
                return self._local_scan_gather(global_params, init, upd,
                                               x_row, y_row, plan, m, n,
                                               feat, global_params,
                                               proximal, vary)

            stacked = _client_map(one_client,
                                  (xg, yg, plans, mask, batch_n),
                                  cfg.cohort_vmap_width)
            partial = jax.tree.map(
                lambda leaf: jnp.tensordot(weights,
                                           leaf.astype(jnp.float32),
                                           axes=1),
                stacked)
            return stacked, partial

        return core

    def _build_train_gather(self):
        core = self._build_train_gather_core()

        def train(global_params, class_x, class_y, rows, plans, mask,
                  batch_n, weights, feat):
            _, partial = core(global_params, class_x, class_y, rows,
                              plans, mask, batch_n, weights, feat)
            return jax.tree.map(lambda p, g: p.astype(g.dtype),
                                partial, global_params)

        return jax.jit(train, static_argnames="feat")

    def _build_train_gather_updates(self):
        """Per-client flat-delta twin of ``_build_train_gather`` for the
        defended aggregation path: one compiled program per (class,
        tier) shape — warmed alongside the aggregate programs by
        DeviceRuntime.warmup when defenses are on, so the warm loop
        still never retraces — returning the (C_cap, D) update matrix
        (padding rows all-zero: masked scans are the identity, so a
        padded row's params equal the globals)."""
        core = self._build_train_gather_core()

        def train(global_params, class_x, class_y, rows, plans, mask,
                  batch_n, feat):
            stacked, _ = core(global_params, class_x, class_y, rows,
                              plans, mask, batch_n,
                              jnp.zeros((rows.shape[0],), jnp.float32),
                              feat)
            return self._flat_deltas(stacked, global_params)

        return jax.jit(train, static_argnames="feat")

    def _build_train_gather_sharded(self):
        """Mesh-mapped twin of ``_build_train_gather``: the class store
        stays replicated (each device gathers its own winners' rows), the
        per-invocation tensors shard their client axis over 'data', and
        the FedAvg partial is psum-reduced on-mesh."""
        from repro.sharding.rules import (cohort_param_spec,
                                          fleet_class_specs)
        core = self._build_train_gather_core(vary=_varying)

        def train(global_params, class_x, class_y, rows, plans, mask,
                  batch_n, weights, feat):
            def shard_body(params, *args):
                _, partial = core(params, *args, feat)
                return jax.tree.map(
                    lambda p, g: jax.lax.psum(p, "data").astype(g.dtype),
                    partial, params)

            return jax.shard_map(
                shard_body, mesh=self.mesh,
                in_specs=(cohort_param_spec(),) + fleet_class_specs(),
                out_specs=cohort_param_spec())(
                    global_params, class_x, class_y, rows, plans, mask,
                    batch_n, weights)

        return jax.jit(train, static_argnames="feat")

    def _build_weight_features(self):
        cfg = self.cfg
        init, upd = sgd(cfg.lr)   # the feature pass uses plain SGD

        def features(global_params, xb, yb, mask):
            def one_client(cx, cy, cm):
                p = self._local_scan(global_params, init, upd, cx, cy, cm,
                                     global_params, proximal=False)
                delta = jax.tree.map(lambda a, b: a - b, p, global_params)
                return _flatten_tree(delta)

            return _client_map(one_client, (xb, yb, mask),
                               self.cfg.cohort_vmap_width)

        return features

    def _build_gradient_features(self):
        def features(params, xb, yb):
            def one_client(cx, cy):
                def body(_, inp):
                    xs, ys = inp
                    g = self.adapter.grad(params, {"x": xs, "y": ys})
                    return None, _flatten_tree(g)

                _, flats = jax.lax.scan(body, None, (cx, cy))
                return flats.mean(0)

            return _client_map(one_client, (xb, yb),
                               self.cfg.cohort_vmap_width)

        return features

    # ------------------------------------------------------------------
    def train_bucket(self, global_params, bucket: CohortBucket
                     ) -> Tuple[Any, Any]:
        """Returns (stacked per-client params with leading C axis,
        weighted partial aggregate sum_c w_c * params_c).  The stacked
        trees are for inspection/tests; the round loop uses
        :meth:`train_cohort`, which skips materializing them."""
        return self._train(global_params, bucket.xb, bucket.yb,
                           bucket.step_mask, bucket.weights,
                           return_stacked=True)

    def train_cohort(self, global_params, buckets: List[CohortBucket]):
        """Aggregated params over all buckets, or None for an empty
        cohort.  Weights are global, so bucket partials just add.  With a
        mesh, each bucket runs mesh-mapped (client axis over 'data') and
        its partial arrives already psum-reduced and replicated."""
        step = self._train_sharded if self._train_sharded is not None \
            else self._train
        agg = None
        for b in buckets:
            self._note_shape(("bucket", b.xb.shape))
            part = step(global_params, b.xb, b.yb, b.step_mask, b.weights)
            agg = part if agg is None else jax.tree.map(
                jnp.add, agg, part)
        return agg

    def train_bucket_updates(self, global_params, bucket: CohortBucket
                             ) -> jnp.ndarray:
        """(C, D) float32 per-client flat deltas for one bucket — the
        defended aggregation path's stage-3 output (padding rows are
        all-zero; bucket.client_idx marks them -1).  Compiles per bucket
        shape like ``train_bucket`` (the defended path adds no *warm*
        retraces beyond the bucket shapes the plain path already pays)."""
        if self._train_updates is None:
            self._train_updates = self._build_train_updates()
        self._note_shape(("bucket_upd", bucket.xb.shape))
        return self._train_updates(global_params, bucket.xb, bucket.yb,
                                   bucket.step_mask)

    def train_class(self, global_params, class_x, class_y, rows, plans,
                    step_mask, batch_n, weights, *, feat):
        """One capacity-class invocation of the device-resident round
        trainer: ``class_x/class_y`` are the class's resident ``(P,
        n_rows, width)`` / ``(P, n_rows)`` store of flat sample rows
        (repro.sim.fleet.CapacityClass), ``feat`` a sample's shape, the
        rest are the per-round ``(C_cap, ...)`` index/weight
        tensors (repro.sim.fleet.ClassBatch).  Returns the weighted
        FedAvg partial over this invocation's winners; partials across
        invocations just add (weights are global)."""
        self._note_shape(("class", class_x.shape, plans.shape))
        step = self._train_gather_sharded \
            if self._train_gather_sharded is not None \
            else self._train_gather
        return step(global_params, class_x, class_y, rows, plans,
                    step_mask, batch_n, weights, feat=feat)

    def train_class_updates(self, global_params, class_x, class_y, rows,
                            plans, step_mask, batch_n, *, feat
                            ) -> jnp.ndarray:
        """(C_cap, D) float32 flat deltas for one capacity-class
        invocation — the device runtime's defended-path twin of
        :meth:`train_class`.  Always the single-device program (the
        screened reduction downstream is single-device; the replicated
        class store makes that correct on any mesh)."""
        if self._train_gather_updates is None:
            self._train_gather_updates = self._build_train_gather_updates()
        self._note_shape(("class_upd", class_x.shape, plans.shape))
        return self._train_gather_updates(global_params, class_x, class_y,
                                          rows, plans, step_mask, batch_n,
                                          feat=feat)

    def weight_features(self, global_params, buckets: List[CohortBucket],
                        num_clients: int) -> jnp.ndarray:
        """(N, D) weight-delta features in original client order."""
        rows = [None] * num_clients
        for b in buckets:
            feats = self._weight_feats(global_params, b.xb, b.yb,
                                       b.step_mask)
            for row, cid in enumerate(b.client_idx):
                if cid >= 0:
                    rows[int(cid)] = feats[row]
        missing = [i for i, r in enumerate(rows) if r is None]
        if missing:
            raise ValueError(
                f"clients {missing} missing from the packed buckets: "
                f"expected every id in [0, {num_clients}) exactly once "
                "(zero-size clients are dropped by the packer and have no "
                "weight-delta feature)")
        return jnp.stack(rows)

    def gradient_features(self, params, xb, yb) -> jnp.ndarray:
        """(N, D) mean sample-window gradients; ``xb (N, T0, window,
        *feat)``, ``yb (N, T0, window)`` (uniform window — no buckets)."""
        return self._grad_feats(params, xb, yb)
