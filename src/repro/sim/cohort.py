"""Host-side plans for stage 3 and the stage-1 weight feature pass.

The sequential oracle (``SequentialRuntime.train_client``) iterates, per
client, ``local_epochs`` shuffled passes of full minibatches of size
``bs = min(32, n)`` and *drops the remainder batch* — so every executed
step sees exactly ``bs`` real samples.  ``oracle_batch_plan`` is that
plan; :class:`HostPlanCache` memoizes everything about it except the
permutation values — a client's batch size, step count and ``x[shard]``
local data gather depend only on the shard — so per-round work rebuilds
only the permutations.  The ``device`` runtime's ``FleetStore``
(repro.sim.fleet) builds its per-round index plans from the cache, and
the shuffle stream matches the oracle bit-for-bit: the same
``np.random.default_rng(history * 977 + client_idx)`` seed and the same
per-epoch ``permutation`` draws.

:func:`pack_feature_pass` packs *all* clients for the clustering
weight-feature pass (``CohortEngine.weight_features``) into dense,
padded minibatch tensors, the :class:`CohortBucket`.  Packing never pads
*inside* a batch; it only pads along

  * the **step axis** — clients with fewer steps than the bucket maximum
    get trailing dummy steps whose per-step mask is 0 (the engine turns a
    masked step into the identity), and
  * the **client axis** — each bucket is padded to a multiple of the
    engine's vmap chunk width with fully masked dummy clients
    (client id -1).

Clients with different batch sizes (only those with fewer than 32 local
samples) cannot share a tensor, and clients with wildly different step
counts would waste compute on padding, so the fleet is split into
**buckets** keyed by ``(batch size, power-of-two step band)``: within a
bucket no client runs more than ~2x the steps of another.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


@dataclass
class CohortBucket:
    """One homogeneous slice of the packed clients (shared batch size).

    Shapes: ``xb (C, S, bs, *feat)``, ``yb (C, S, bs)``, ``step_mask
    (C, S)`` float32 (1 = real step), ``client_idx (C,)`` int32 global
    client ids (-1 for padding).
    """

    client_idx: np.ndarray
    xb: np.ndarray
    yb: np.ndarray
    step_mask: np.ndarray
    batch_size: int

    @property
    def num_clients(self) -> int:
        return int(self.client_idx.shape[0])

    @property
    def num_steps(self) -> int:
        return int(self.step_mask.shape[1])


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def drop_zero_size_winners(sel_idx: np.ndarray, clients) -> np.ndarray:
    """Winners with no local samples run no steps and carry no FedAvg
    mass — drop them before weighting (the sequential oracle's rule;
    ``FleetStore.assemble`` applies the same one)."""
    sel_idx = np.asarray(sel_idx)
    if sel_idx.size == 0:
        return sel_idx
    return sel_idx[[clients[int(i)].size > 0 for i in sel_idx]]


def oracle_batch_plan(n: int, bs: int, epochs: int,
                      rng: np.random.Generator) -> np.ndarray:
    """The exact (epochs * steps, bs) local-index plan the sequential
    oracle executes: per epoch one ``rng.permutation(n)`` draw, then full
    minibatches of ``bs`` with the remainder dropped."""
    steps = (n - bs) // bs + 1 if n >= bs else 0
    out = np.empty((epochs * steps, bs), np.int64)
    r = 0
    for _ in range(epochs):
        order = rng.permutation(n)
        for i in range(0, n - bs + 1, bs):
            out[r] = order[i:i + bs]
            r += 1
    return out


def sequential_batch_plan(n: int, bs: int) -> np.ndarray:
    """The clustering feature pass's plan: one epoch, natural order, full
    minibatches, remainder dropped (mirrors the ``local_steps_fn`` loop)."""
    steps = (n - bs) // bs + 1 if n >= bs else 0
    return np.arange(steps * bs, dtype=np.int64).reshape(steps, bs)


class HostPlanCache:
    """Per-client plan structure and local data shards, memoized once.

    ``oracle_batch_plan`` is (permutation, structure): the batch size,
    per-epoch step count and batch boundaries depend only on the shard
    size and ``local_epochs``; only the permutation values depend on the
    history-seeded rng.  The cache precomputes the structure (and the
    ``x[shard]``/``y[shard]`` local copies, gathered lazily once per
    client) so a round rebuilds just the per-epoch permutations, and the
    fleet store and the feature pass gather from the small contiguous
    local arrays instead of the global pool.

    :meth:`plan` returns *local* sample indices (into the client's own
    shard), bit-identical to ``shardless`` composition of the oracle:
    ``shard[oracle_batch_plan(...)] == local_data[plan(...)]`` row for
    row (tests/test_fleet.py).
    """

    def __init__(self, x: np.ndarray, y: np.ndarray, clients,
                 epochs: int):
        self.epochs = int(epochs)
        self._x, self._y = x, y
        self.shards = [np.asarray(c.train_idx) for c in clients]
        self.sizes = np.array([len(s) for s in self.shards], np.int64)
        self.bs = np.minimum(32, self.sizes)
        # full minibatches of bs with the remainder dropped = n // bs
        self.steps = np.where(self.sizes > 0,
                              self.sizes // np.maximum(self.bs, 1), 0)
        self._local: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}

    def local_data(self, i: int) -> Tuple[np.ndarray, np.ndarray]:
        """(x[shard], y[shard]) for client ``i``, gathered once."""
        got = self._local.get(i)
        if got is None:
            s = self.shards[i]
            got = self._local[i] = (self._x[s], self._y[s])
        return got

    def drop_local_data(self) -> None:
        """Release the memoized host copies (lazily re-gathered on next
        use).  The device runtime calls this after the fleet store has
        packed them onto the device — keeping a full host duplicate of
        the pool alive for the whole run would defeat 'pack once'."""
        self._local.clear()

    def plan(self, i: int, history_count: int) -> np.ndarray:
        """The oracle's (epochs * steps, bs) plan in LOCAL indices: only
        the ``rng.permutation`` draws are recomputed per call."""
        n, bs = int(self.sizes[i]), int(self.bs[i])
        s = int(self.steps[i])
        rng = np.random.default_rng(int(history_count) * 977 + int(i))
        out = np.empty((self.epochs * s, bs), np.int64)
        for e in range(self.epochs):
            order = rng.permutation(n)
            out[e * s:(e + 1) * s] = order[:s * bs].reshape(s, bs)
        return out


def _pack_plans(locals_xy: Sequence[Tuple[np.ndarray, np.ndarray]],
                plans: Sequence[np.ndarray],
                client_ids: Sequence[int],
                chunk_width: int = 4) -> List[CohortBucket]:
    """Group (plan, local shard) pairs into (batch size, pow2 step band)
    buckets and materialize the padded tensors.  ``locals_xy[m]`` holds
    member m's (x_local, y_local) data and ``plans[m]`` indexes into it
    (local indices)."""
    by_key: Dict[tuple, List[int]] = {}
    for pos, plan in enumerate(plans):
        key = (plan.shape[1], _next_pow2(max(plan.shape[0], 1)))
        by_key.setdefault(key, []).append(pos)

    x0, y0 = locals_xy[0]
    buckets = []
    for (bs, _band), members in sorted(by_key.items()):
        s_max = _round_up(max(plans[m].shape[0] for m in members), 4)
        # multiple of the vmap chunk width, but never beyond next-pow2
        # (a 2-client bucket padded to 4 would double its compute)
        c_pad = min(_round_up(len(members), chunk_width),
                    _next_pow2(len(members)))
        xb = np.zeros((c_pad, s_max, bs) + x0.shape[1:], x0.dtype)
        yb = np.zeros((c_pad, s_max, bs), y0.dtype)
        mask = np.zeros((c_pad, s_max), np.float32)
        cid = np.full((c_pad,), -1, np.int32)
        for row, m in enumerate(members):
            plan = plans[m]
            xl, yl = locals_xy[m]
            s = plan.shape[0]
            xb[row, :s] = xl[plan]                     # (s, bs, *feat)
            yb[row, :s] = yl[plan]
            mask[row, :s] = 1.0
            cid[row] = client_ids[m]
        buckets.append(CohortBucket(client_idx=cid, xb=xb, yb=yb,
                                    step_mask=mask, batch_size=bs))
    return buckets


def pack_feature_pass(x: np.ndarray, y: np.ndarray, clients,
                      chunk_width: int = 4,
                      cache: Optional[HostPlanCache] = None
                      ) -> List[CohortBucket]:
    """Pack *all* clients for the clustering weight-feature pass: one
    in-order epoch per client (no shuffle); features are returned per
    client, not aggregated."""
    if cache is None:
        cache = HostPlanCache(x, y, clients, 1)
    locals_xy, plans = [], []
    for i in range(len(clients)):
        locals_xy.append(cache.local_data(i))
        plans.append(sequential_batch_plan(int(cache.sizes[i]),
                                           int(cache.bs[i])))
    ids = list(range(len(clients)))
    return _pack_plans(locals_xy, plans, ids, chunk_width=chunk_width)
