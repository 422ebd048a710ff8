"""FleetStore: device-resident fleet data + compile-once capacity classes.

Packing a round's winners into padded ``(C, S, bs, *feat)`` minibatch
tensors on the host would pay an H2D copy of every sample every round;
worse, such shapes are *data-dependent* — ``(batch size, pow2 step
band)`` over whichever clients won the auction — so jit would retrace
whenever a round's cohort composition shifts.  The ``device`` runtime
avoids both:

* **Pack once.**  At server init every client's local shard is gathered
  once into a device-resident per-class store of flat sample rows
  ``(P, n_rows, width)`` (row-major by client, plus size/step tables).
  Per-round cohort assembly is then an on-device ``jnp.take`` by winner
  rows inside the compiled program — the only thing the host builds per
  round are tiny int32 index tensors (winner rows + local batch plans,
  i.e. the oracle's shuffle permutations, which must stay on the host
  rng to remain bit-compatible with the sequential oracle).
  The program cuts each step's gathered ``(bs, width)`` minibatch to
  its ``prod(feat)`` values and reshapes it to ``(bs, *feat)`` just
  before the model sees it.

* **Read only the winners.**  On a TPU what a row gather reads depends
  on the store's device layout, which the chip picks per shape to pad
  the fewest ``(8, 128)`` tiles.  A store of images ``(P, n, 28, 28,
  1)``, or of flat rows ``(P, n, 784)`` whose axes are not whole tiles,
  can get a layout that puts the member axis on a tile; every class call
  then relayouts (for images also casts) the *whole* store, all ``P``
  members, before it takes the winners' rows: bytes that grow with the
  fleet, not the cohort.  So a store pads its sample axis to whole sublanes (``n_rows``,
  the class's largest shard ``n_cap`` rounded up to 8) and its rows to
  whole lanes (``width``, ``prod(feat)`` rounded up to 128).  Its
  row-major layout then pads nothing, the chip keeps it, the member
  axis stays major, and a call reads only the rows it gathers.  The
  padding costs under 8 samples a member and 112 floats a 784-float
  row.

* **In range by construction.**  ``assemble`` writes winner rows in
  ``[0, P)`` (padding rows 0) and plan indices in ``[0, n)`` (padding 0),
  so the program's takes use ``mode="clip"``: exact for in-range
  indices, and without the out-of-range fill that ``jnp.take``'s default
  mode selects over every gathered minibatch.

* **Compile once.**  Bucket shapes are replaced by a small static set of
  **capacity classes** derived from the *fleet* at init, not the round's
  cohort: class key = pow2 band of total local steps, batch size = the
  largest member's ``min(32, n)`` (a member with a smaller shard pads its
  batch axis under a per-sample mask, so a fleet's many shards smaller
  than a batch share the full-batch class of their band instead of one
  class per size), step capacity = the class's fleet-wide max (rounded
  to a multiple of 4),
  client capacity = a short pow2 **tier ladder** up to the per-round
  winner bound (each tier rounded to a multiple of the mesh data-axis
  size).  Every possible winner maps to a pre-known class and every
  possible winner count to a pre-known tier, so ``CohortEngine
  .train_class`` compiles once per (class, tier) at warm-up and never
  retraces; a round whose winners in one class exceed the top tier
  simply runs the *same* compiled programs more than once (greedy
  largest-fitting-tier chunking).

Padding waste bound: within a class the pow2 step band keeps any member
below ~2x the steps of the smallest; a member whose shard is smaller
than the class batch runs its single step per epoch over its n samples,
the batch's other slots weighing 0 in the loss
(``ModelAdapter.masked_grad``); the pow2 tier ladder keeps client-axis
padding below 2x the invocation's real winner count (masked rows are
weight-0 and drop out of the FedAvg sum exactly), at the cost of one
warm-up compile per (class, tier).  See DESIGN.md §Round pipeline.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import FLConfig
from repro.core.selection import k_per_cluster
from repro.sim.cohort import HostPlanCache, _next_pow2, _round_up

# the TPU's float32 tile (sublanes, lanes): a class store's sample axis
# and row width are whole tiles, so that the chip keeps the store's
# member axis major (module docstring)
TILE = (8, 128)


def store_shape(members: int, n_cap: int, feat: tuple) -> tuple:
    """``(P, n_rows, width)`` of the flat class store of ``members``
    clients whose largest shard holds ``n_cap`` samples of shape
    ``feat``: both sample axes padded to whole tiles (:data:`TILE`)."""
    return (members, _round_up(n_cap, TILE[0]),
            _round_up(int(np.prod(feat)), TILE[1]))


@dataclass
class CapacityClass:
    """One static shape class of the fleet (one compiled program per
    client-capacity tier).

    ``x (P, n_rows, width)`` / ``y (P, n_rows)`` are the device-resident
    local shards of the class's ``P`` members as flat sample rows: a
    sample of shape ``feat`` is the first ``prod(feat)`` of its row's
    ``width`` values, and each member is padded past the class max size
    ``n_cap`` to ``n_rows`` samples (plans never index the padding;
    :func:`store_shape`).  ``tiers`` is the ascending pow2 ladder of
    padded client-axis sizes an invocation may use (every tier a
    multiple of the mesh data-axis size).
    """

    bs: int
    step_cap: int            # padded step axis (multiple of 4)
    tiers: List[int]         # padded client-axis capacities (ascending)
    n_cap: int
    members: np.ndarray      # (P,) global client ids
    feat: tuple              # one sample's shape
    x: jnp.ndarray
    y: jnp.ndarray

    @property
    def client_cap(self) -> int:
        """Largest per-invocation client capacity (the top tier)."""
        return self.tiers[-1]


@dataclass
class ClassBatch:
    """One per-round invocation of a capacity class's program.

    ``rows (C_cap,)`` int32 rows into the class store (0 for padding —
    masked out), ``plans (C_cap, step_cap, bs)`` int32 local sample
    indices, ``step_mask (C_cap, step_cap)`` float32, ``weights (C_cap,)``
    float32 *global* FedAvg weights (over all invocations they sum to 1),
    ``batch_n (C_cap,)`` int32 real samples in each of a row's steps
    (the first ``batch_n`` of its ``bs`` plan slots; ``bs`` where the
    steps fill the batch), ``client_idx (C_cap,)`` int32 global ids (-1
    for padding).
    """

    cls_id: int
    rows: np.ndarray
    plans: np.ndarray
    step_mask: np.ndarray
    batch_n: np.ndarray
    weights: np.ndarray
    client_idx: np.ndarray


class FleetStore:
    """Pack the whole fleet once; assemble cohorts as index tensors."""

    def __init__(self, x: np.ndarray, y: np.ndarray, clients,
                 cfg: FLConfig, client_multiple: int = 1,
                 cache: HostPlanCache | None = None):
        self.cfg = cfg
        self.cache = cache if cache is not None \
            else HostPlanCache(x, y, clients, cfg.local_epochs)
        n = len(clients)
        total_steps = self.cache.steps * cfg.local_epochs
        self.class_of = np.full((n,), -1, np.int64)
        self.row_of = np.full((n,), -1, np.int64)

        groups: Dict[tuple, List[int]] = {}
        for i in range(n):
            if self.cache.sizes[i] == 0:     # no steps, no FedAvg mass
                continue
            groups.setdefault(_next_pow2(max(int(total_steps[i]), 1)),
                              []).append(i)

        # per-round winner bound: k_total overall, but per-cluster floors
        # can push the union above it (num_clusters x K_j)
        k_total = max(int(round(cfg.select_ratio * cfg.num_clients)), 1)
        k_bound = max(k_total, cfg.num_clusters * k_per_cluster(cfg))
        mult = max(int(client_multiple), 1)

        self.classes: List[CapacityClass] = []
        for _band, members in sorted(groups.items()):
            members = np.asarray(members, np.int64)
            n_cap = int(self.cache.sizes[members].max())
            bs = int(self.cache.bs[members].max())
            step_cap = _round_up(int(total_steps[members].max()), 4)
            cap = min(len(members), k_bound)
            # pow2 ladder 1, 2, 4, ... up to the winner bound, every tier
            # rounded to the mesh data-axis multiple (rounding collapses
            # small tiers on big meshes — dedupe keeps the set tight)
            tiers, t = [], 1
            while t < cap:
                tiers.append(_round_up(t, mult))
                t *= 2
            tiers.append(_round_up(cap, mult))
            tiers = sorted(set(tiers))
            feat = tuple(x.shape[1:])
            size = int(np.prod(feat))
            xb = np.zeros(store_shape(len(members), n_cap, feat), x.dtype)
            yb = np.zeros(xb.shape[:2], y.dtype)
            for r, gid in enumerate(members):
                xl, yl = self.cache.local_data(int(gid))
                xb[r, :len(xl), :size] = xl.reshape(len(xl), size)
                yb[r, :len(yl)] = yl
                self.class_of[gid] = len(self.classes)
                self.row_of[gid] = r
            # the one-time fleet pack IS a real host->device transfer —
            # route it through the counted explicit wrapper so the obs
            # byte books include it and the warm loop stays implicit-free
            xd, yd = obs.device_put((xb, yb))
            self.classes.append(CapacityClass(
                bs=bs, step_cap=step_cap, tiers=tiers, n_cap=n_cap,
                members=members, feat=feat, x=xd, y=yd))

    # ------------------------------------------------------------------
    def _empty_batch(self, cls_id: int, tier: int) -> ClassBatch:
        c = self.classes[cls_id]
        return ClassBatch(
            cls_id=cls_id,
            rows=np.zeros((tier,), np.int32),
            plans=np.zeros((tier, c.step_cap, c.bs), np.int32),
            step_mask=np.zeros((tier, c.step_cap), np.float32),
            batch_n=np.full((tier,), c.bs, np.int32),
            weights=np.zeros((tier,), np.float32),
            client_idx=np.full((tier,), -1, np.int32))

    def warmup_batches(self) -> List[ClassBatch]:
        """One fully-masked invocation per (class, tier): running each
        through ``CohortEngine.train_class`` compiles every program the
        fleet can ever need (classes and tiers are static), so the round
        loop never traces."""
        return [self._empty_batch(i, t)
                for i, c in enumerate(self.classes) for t in c.tiers]

    def assemble(self, sel_idx: np.ndarray,
                 history: np.ndarray) -> List[ClassBatch]:
        """Index tensors for the round's winners.  ``history`` is the
        pre-round host participation mirror (seeds the shuffle rng).
        Zero-size winners are dropped (the oracle's rule); an
        all-zero cohort assembles to [] — skip aggregation."""
        sel_idx = np.asarray(sel_idx)
        if sel_idx.size:
            sel_idx = sel_idx[self.cache.sizes[sel_idx] > 0]
        if sel_idx.size == 0:
            return []
        sizes = self.cache.sizes[sel_idx].astype(np.float64)
        pk = sizes / sizes.sum()

        by_cls: Dict[int, List[tuple]] = {}
        for i, p in zip(sel_idx, pk):
            by_cls.setdefault(int(self.class_of[int(i)]), []).append(
                (int(i), float(p)))

        out = []
        for cls_id, winners in sorted(by_cls.items()):
            c = self.classes[cls_id]
            lo = 0
            while lo < len(winners):
                rem = len(winners) - lo
                # greedy largest tier that the remainder fills; when even
                # the smallest tier is bigger, take it (padding < 2x rem)
                fits = [t for t in c.tiers if t <= rem]
                tier = fits[-1] if fits else c.tiers[0]
                chunk = winners[lo:lo + tier]
                lo += len(chunk)
                b = self._empty_batch(cls_id, tier)
                for r, (gid, p) in enumerate(chunk):
                    plan = self.cache.plan(gid, int(history[gid]))
                    s, n = plan.shape
                    b.rows[r] = self.row_of[gid]
                    b.plans[r, :s, :n] = plan
                    b.step_mask[r, :s] = 1.0
                    b.batch_n[r] = n
                    b.weights[r] = p
                    b.client_idx[r] = gid
                out.append(b)
        return out


def class_work(batches: List[ClassBatch],
               chunks: Callable[[int], int]) -> Dict[str, int]:
    """What a round's class calls run: ``calls``; ``serial_steps``, the
    local-SGD steps they run one after another (a call runs its class's
    ``step_cap`` steps, masked or not, once per client chunk:
    ``chunks(tier)``); ``step_slots``, the client-step slots they carry
    (tier x step_cap); ``steps_real``, the unmasked ones;
    ``sample_slots``, the batch-axis slots of those real steps (steps x
    the class's bs); ``samples_real``, the real samples among them."""
    steps = [b.step_mask.sum(axis=1) for b in batches]    # per row
    return {"calls": len(batches),
            "serial_steps": sum(b.step_mask.shape[1]
                                * chunks(b.step_mask.shape[0])
                                for b in batches),
            "step_slots": sum(b.step_mask.size for b in batches),
            "steps_real": int(sum(s.sum() for s in steps)),
            "sample_slots": int(sum(s.sum() * b.plans.shape[2]
                                    for s, b in zip(steps, batches))),
            "samples_real": int(sum((s * b.batch_n).sum()
                                    for s, b in zip(steps, batches)))}
