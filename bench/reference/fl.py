"""Plain reference of one round of the paper's auction-based clustered FL
(arXiv:2103.07150, Algorithm 1 stages 2 and 3), and the comparison that
decides whether a round's answers are correct.

Stage 2, in NumPy: each client's cost (eq 12-14, resource cost on the
battery fraction, service cost with the intended sign of the history
term), its symmetric Nash bid (Theorem 2), the sample threshold from a
random probe cluster, the per-cluster reverse auction (the K_j lowest
eligible bids, ties broken by service cost, then client index), and the
energy and participation update (eq 9-11).  The random probe cluster is
drawn from the round's key with ``jax.random`` as the paper's system
draws it: ``randint(split(key, 4)[0], (), 0, J)``.

Stage 3: every winner runs ``epochs`` of plain SGD over full minibatches
of ``min(32, n)`` samples, shuffled by
``numpy.random.default_rng(participations * 977 + client)``, from the
round's global weights, and FedAvg weighs the results by local size.

``dtype`` sets the precision: float64 (stage 2) and float32 (stage 3,
with the products' operands rounded to the type the configuration states,
``Fleet.operands``) are the reference; bfloat16 throughout is the
control.  Stage 3 runs on the host's CPU device: XLA's TPU compiler takes
many minutes over the gradient of a convolution at ``highest``
precision.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from bench.reference import cnn_mnist as M

BF16 = ml_dtypes.bfloat16
# the configuration's ``matmul_operands``: the type every product rounds
# its two operands to
OPERANDS = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
# a leaf whose reference update is under this share of the median leaf's
# moves by rounding alone and is left out of ``update_gap``
STILL_LEAF = 1e-3
# bids this close, relative to each other, are a tie that float32
# arithmetic may order either way; ``winner_bid_gap``'s limit admits the
# same gap between the bids of two winners
BID_TIE = 1e-4


@dataclass
class RoundInputs:
    """What a round starts from: the selection state, the round key and
    the global weights (host arrays)."""

    clusters: np.ndarray
    residual: np.ndarray
    history: np.ndarray
    key: np.ndarray
    params: Dict[str, np.ndarray]


@dataclass
class RoundAnswers:
    """What a round produces: winners, the selection state after it, the
    aggregated weights and, on an eval round, the test loss."""

    win: np.ndarray
    residual: np.ndarray
    history: np.ndarray
    params: Dict[str, np.ndarray]
    eval_loss: Optional[float]


class Fleet:
    """The data and the constants every round reads: the image pool, each
    client's training indices, the test batch, and the FL constants of
    the configuration (``fl`` section of its file), and the type the
    configuration's products round their operands to."""

    def __init__(self, x, y, train_idx, x_test, y_test, fl: dict,
                 operands):
        self.x, self.y = x, y
        self.operands = operands          # see bench.reference.cnn_mnist
        self.train_idx = train_idx
        self.sizes = np.array([len(t) for t in train_idx], np.int64)
        self.x_test, self.y_test = x_test, y_test
        self.c = fl

    @property
    def k_per_cluster(self) -> int:
        c = self.c
        k_total = max(int(round(c["select_ratio"] * c["num_clients"])), 1)
        return max(k_total // c["num_clusters"], 1)


# ----------------------------------------------------------------------
# stage 2
# ----------------------------------------------------------------------

def price(fleet: Fleet, inp: RoundInputs, dt):
    """(bids, service costs, affordable) in ``dt``; an unaffordable
    client's bid is +inf."""
    c = fleet.c
    f = lambda v: np.asarray(v, dt)  # noqa: E731
    sizes = f(fleet.sizes)
    e_cp = sizes * f(c["energy_per_100_samples"]) / f(100.0)
    margin = (f(inp.residual) - e_cp) / f(100.0)
    afford = margin > 0
    cr = np.power(f(c["phi"]), margin)
    hist = np.log(f(inp.history) + f(c["log_a"])) / np.log(f(c["log_a"]))
    cs = (f(c["chi"]) * np.power(f(c["vartheta"]), sizes)
          + f(c["zeta"]) * (hist - f(1.0)))
    cost = np.clip(f(c["alpha"]) * cs + f(c["gamma"]) * cr, f(0.0), f(1.0))
    nj = np.bincount(inp.clusters, minlength=c["num_clusters"])
    d = f(np.maximum(nj[inp.clusters] - fleet.k_per_cluster, 0))
    bid = f(1.0) / (d + f(1.0)) + d / (d + f(1.0)) * cost
    bid = np.where(afford, bid, f(np.inf))
    return bid, cs, afford


def probe_cluster(fleet: Fleet, inp: RoundInputs) -> int:
    """The cluster whose lowest bids set the round's size threshold."""
    k0 = jax.random.split(np.asarray(inp.key, np.uint32), 4)[0]
    return int(jax.random.randint(k0, (), 0, fleet.c["num_clusters"]))


def size_floors(sizes: np.ndarray, bid: np.ndarray, members: np.ndarray,
                kj: int) -> list:
    """The size thresholds a probe of the ``kj`` lowest bids among
    ``members`` can set: the exact one first, then those that a tie
    within ``BID_TIE`` at the ``kj``-th bid, ordered the other way, sets."""
    if members.size == 0:
        return [0]
    order = members[np.argsort(bid[members], kind="stable")]
    exact = int(sizes[order[:kj]].min())
    if members.size <= kj:
        return [exact]
    bk = bid[order[kj - 1]]
    lo, hi = bk - BID_TIE * abs(bk), bk + BID_TIE * abs(bk)
    sure = members[bid[members] < lo]
    maybe = sizes[members[(bid[members] >= lo) & (bid[members] <= hi)]]
    base = sizes[sure].min() if sure.size else np.inf
    need = kj - sure.size
    out = {int(min(base, s)) for s in maybe if (maybe >= s).sum() >= need}
    return [exact] + sorted(out - {exact})


def select(fleet: Fleet, inp: RoundInputs, dt, smin=None):
    """(winner mask, bids as float64) of the round; ``smin`` replaces the
    size threshold the probe sets."""
    c = fleet.c
    kj = fleet.k_per_cluster
    bid, cs, afford = price(fleet, inp, dt)
    b64 = np.asarray(bid, np.float64)
    tie = np.clip(np.asarray(cs, np.float64), 0.0, 1.0)
    if smin is None:
        probe = np.flatnonzero((inp.clusters == probe_cluster(fleet, inp))
                               & afford)
        smin = size_floors(fleet.sizes, b64, probe, kj)[0]
    eligible = (fleet.sizes >= smin) & afford
    win = np.zeros(len(fleet.sizes), bool)
    for j in range(c["num_clusters"]):
        m = np.flatnonzero((inp.clusters == j) & eligible)
        win[m[np.lexsort((m, tie[m], b64[m]))][:kj]] = True
    return win, np.where(eligible, b64, np.inf)


def account(fleet: Fleet, inp: RoundInputs, win: np.ndarray, dt):
    """(residual, history) after the winners pay the round's energy."""
    c = fleet.c
    f = lambda v: np.asarray(v, dt)  # noqa: E731
    e = ((f(fleet.sizes) * f(c["energy_per_100_samples"]) / f(100.0)
          + f(c["energy_rx"]) + f(c["energy_tx"])) * f(c["local_epochs"]))
    res = f(inp.residual)
    res = np.where(win, np.maximum(res - e, f(0.0)), res)
    return res, inp.history + win.astype(inp.history.dtype)


# ----------------------------------------------------------------------
# stage 3
# ----------------------------------------------------------------------

def local_train(fleet: Fleet, params, client: int, participations: int,
                dt):
    c = fleet.c
    shard = fleet.train_idx[client]
    n = len(shard)
    bs = min(32, n)
    steps = n // bs
    xl, yl = fleet.x[shard], fleet.y[shard]
    rng = np.random.default_rng(int(participations) * 977 + int(client))
    p = params
    for _ in range(c["local_epochs"]):
        order = rng.permutation(n)
        for s in range(steps):
            idx = order[s * bs:(s + 1) * bs]
            p = M.sgd_step(p, xl[idx], yl[idx], c["lr"], dt,
                           fleet.operands)
    return p


def fedavg(fleet: Fleet, params, win: np.ndarray, history: np.ndarray,
           dt) -> Dict[str, np.ndarray]:
    """FedAvg over the winners' local models (float64 sums for the
    reference, ``dt`` sums for the control), as float32 host arrays."""
    ids = np.flatnonzero(win & (fleet.sizes > 0))
    acc_dt = np.float64 if dt == np.float32 else dt
    w = fleet.sizes[ids].astype(np.float64)
    w = (w / w.sum()).astype(acc_dt)
    acc = None
    for wk, i in zip(w, ids):
        local = jax.device_get(local_train(fleet, params, int(i),
                                           int(history[i]), dt))
        term = {k: np.asarray(v, acc_dt) * wk for k, v in local.items()}
        acc = term if acc is None else {k: acc[k] + term[k] for k in acc}
    if acc is None:
        return {k: np.asarray(v, np.float32) for k, v in params.items()}
    return {k: np.asarray(v, np.float32) for k, v in acc.items()}


def logits(fleet: Fleet, params, operands) -> np.ndarray:
    """The reference's float32 logits of the test batch, with the
    products' operands rounded to ``operands``, on the CPU device."""
    with jax.default_device(jax.devices("cpu")[0]):
        return np.asarray(M.forward(params, fleet.x_test, operands),
                          np.float64)


def eval_loss(fleet: Fleet, params, dt) -> float:
    return float(M.eval_loss(params, fleet.x_test, fleet.y_test, dt,
                             fleet.operands))


def on_cpu(fn):
    """Run ``fn`` with every array it makes placed on the CPU device."""
    def wrapped(*args, **kw):
        with jax.default_device(jax.devices("cpu")[0]):
            return fn(*args, **kw)
    return wrapped


@on_cpu
def answer(fleet: Fleet, inp: RoundInputs, eval_due: bool,
           dt) -> RoundAnswers:
    """A whole round computed by the reference in ``dt``: the control
    (bfloat16) is this, put in the program's place."""
    sel_dt = np.float64 if dt == np.float32 else dt
    win, _ = select(fleet, inp, sel_dt)
    res, hist = account(fleet, inp, win, sel_dt)
    params = fedavg(fleet, inp.params, win, inp.history, dt)
    loss = eval_loss(fleet, params, dt) if eval_due else None
    return RoundAnswers(win=win, residual=np.asarray(res, np.float32),
                        history=hist, params=params, eval_loss=loss)


# ----------------------------------------------------------------------
# the comparison
# ----------------------------------------------------------------------

def winner_bid_gap(fleet: Fleet, inp: RoundInputs, win: np.ndarray,
                   ref_win: np.ndarray, bids: np.ndarray) -> float:
    """Per cluster, |reference bids of ``win`` - of ``ref_win``| over the
    latter, largest over clusters; an ineligible pick bids 1e9."""
    picked = np.where(win, np.minimum(bids, 1e9), 0.0)
    wanted = np.where(ref_win, bids, 0.0)
    gap_max = 0.0
    for j in range(fleet.c["num_clusters"]):
        m = inp.clusters == j
        denom = wanted[m].sum()
        gap = abs(picked[m].sum() - denom)
        gap_max = max(gap_max, gap / denom if denom > 0 else gap)
    return float(gap_max)


@on_cpu
def compare(fleet: Fleet, inp: RoundInputs, got: RoundAnswers,
            leaves: Optional[dict] = None) -> Dict[str, float]:
    """The round's numbers, each measured against the reference:

    * ``winner_bid_gap``: per cluster, the gap between the reference
      bids of the winners ``got`` picked and of the reference's own
      winners, over the latter (largest over clusters; an ineligible
      pick counts as a bid of 1e9); where a tie within ``BID_TIE`` in
      the probe cluster admits other size thresholds, the smallest gap
      over them;
    * ``state_gap``: the largest per-client gap of the selection state
      after the round from the reference's accounting of ``got``'s
      winners (residual energy in percent points, participations in
      rounds);
    * ``update_gap``: the round's weight update against the reference's
      from the same weights and winners, by the worst leaf:
      ``|du - du_ref| / |du_ref|``, over the leaves whose ``|du_ref|`` is
      at least ``STILL_LEAF`` of the median leaf's (a round with no
      update in the reference reads ``|du|`` of the worst leaf);
    * ``eval_loss_gap``: the relative gap of the test loss of ``got``'s
      weights (eval rounds only).

    ``leaves``, when given, gets each counted leaf's update gap."""
    bid, _, afford = price(fleet, inp, np.float64)
    probe = np.flatnonzero((inp.clusters == probe_cluster(fleet, inp))
                           & afford)
    bid_gap = min(
        winner_bid_gap(fleet, inp, got.win, *select(fleet, inp, np.float64,
                                                     smin))
        for smin in size_floors(fleet.sizes, np.asarray(bid, np.float64),
                                probe, fleet.k_per_cluster))

    res, hist = account(fleet, inp, got.win, np.float64)
    state_gap = max(float(np.max(np.abs(got.residual - res))),
                    float(np.max(np.abs(got.history - hist))))

    ref = fedavg(fleet, inp.params, got.win, inp.history, np.float32)
    d_ref = {k: ref[k].astype(np.float64) - inp.params[k] for k in ref}
    d_got = {k: got.params[k].astype(np.float64) - inp.params[k]
             for k in ref}
    norms = {k: np.linalg.norm(v) for k, v in d_ref.items()}
    med = float(np.median(list(norms.values())))
    if med > 0:
        gaps = {k: np.linalg.norm(d_got[k] - d_ref[k]) / norms[k]
                for k in ref if norms[k] >= STILL_LEAF * med}
    else:
        gaps = {k: np.linalg.norm(d_got[k]) for k in ref}
    if leaves is not None:
        leaves.update(gaps)
    update_gap = max(gaps.values())
    out = {"winner_bid_gap": float(bid_gap), "state_gap": state_gap,
           "update_gap": float(update_gap)}
    if got.eval_loss is not None:
        ref_loss = eval_loss(fleet, got.params, np.float32)
        out["eval_loss_gap"] = abs(got.eval_loss - ref_loss) / abs(ref_loss)
    return out
