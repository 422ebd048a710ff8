"""Chip smoke test: the paper's FL trainer end to end on a TPU.

    python3 chip_smoke.py             # one chip: every phase below
    python3 chip_smoke.py --chips 4   # only the four-chip cohort mesh path,
                                      # against the same run on one chip

One process drives the chip through the normal API (``FLConfig`` +
``FederatedServer``, as ``repro.launch.train.run_paper`` does):

  1. require the chip: the first device must be a TPU;
  2. the paper trainer at published width: CNN-MNIST, ``device`` runtime,
     100 clients, 10 clusters, select ratio 0.1, a 60 000-image pool
     (MNIST's training-set size), ``gradient_cluster_auction``, a few
     rounds, the warm rounds under the sync auditor (no implicit host
     transfer);
  3. stage 1 through the compiled Pallas ``lloyd_step``, its labels equal
     to ``impl="ref"`` on the same features;
  4. finite losses and better-than-chance accuracy; then two rounds of
     the same config on ``device`` and on the ``sequential`` reference
     runtime: identical selection, energy and history logs, and each
     round's aggregate, trained by both runtimes from the same params and
     winners, within the tolerance the engine tests use;
  5. the selection-only scan at N = 1 000 000 clients (a smoke reading,
     not a benchmark).

A failed check is reported where it happens and makes the script exit
non-zero after the remaining phases ran; an exception exits at once.  The
last line of stdout is one JSON object naming the device, printed only
when every check passed.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import FLConfig  # noqa: E402
from repro.core import clustering as CL  # noqa: E402
from repro.core import rounds as R  # noqa: E402
from repro.core.adapters import cnn_adapter  # noqa: E402
from repro.core.server import FederatedServer  # noqa: E402
from repro.data.partition import partition_clients  # noqa: E402
from repro.data.synthetic import make_image_dataset  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

SEED = 0
POOL = 60_000          # MNIST's training-set size
ROUNDS = 5
# non-IID level nu (the share of a client's data under its primary label):
# at the default nu = 1 the CNN stays at chance accuracy for the first five
# rounds, so the accuracy check would check nothing.  Local epochs and lr
# stay at FLConfig's defaults: more local steps per round learn faster but
# amplify float-reassociation differences between runtimes within a round
# (five epochs: 8.9e-5 after round 1 on a CPU, next to PARAM_TOL)
NON_IID = 0.2
# runs compared against a reference stop after this many rounds; each
# round's aggregate is held to the params tolerance tests/test_sim.py uses
REF_ROUNDS = 2
PARAM_TOL = 1e-4
# RoundLog fields the control plane computes; identical across runtimes
LOG_FIELDS = ("energy_std", "mean_bid", "server_reward",
              "client_reward_sum", "vds_gap")
SCAN_CLIENTS, SCAN_CLUSTERS, SCAN_ROUNDS = 1_000_000, 100, 20


class Checks:
    """Prints each check as it is made and remembers the failed ones."""

    def __init__(self):
        self.failed = []

    def __call__(self, ok: bool, what: str) -> bool:
        print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
        if not ok:
            self.failed.append(what)
        return ok


def paper_cfg(**kw) -> FLConfig:
    base = dict(num_clients=100, num_clusters=10, select_ratio=0.1,
                rounds=ROUNDS, non_iid_level=NON_IID,
                scheme="gradient_cluster_auction",
                init_energy_mode="normal", runtime="device", seed=SEED)
    base.update(kw)
    return FLConfig(**base)


def make_server(cfg: FLConfig, data) -> FederatedServer:
    train, test = data
    clients = partition_clients(train.y, cfg, seed=SEED)
    ntest = min(1000, len(test.x))
    return FederatedServer(cfg, cnn_adapter("mnist"), train.x, train.y,
                           clients, {"x": test.x[:ntest],
                                     "y": test.y[:ntest]})


def max_param_diff(a, b):
    """(largest |a - b| over all params, the leaf that holds it)."""
    return max((float(jnp.max(jnp.abs(x - y))), jax.tree_util.keystr(path))
               for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                                       jax.tree.leaves(b)))


def first_log_divergence(a, b):
    """Where two runs' round logs first differ, or None."""
    if len(a) != len(b):
        return f"{len(a)} rounds vs {len(b)}"
    for la, lb in zip(a, b):
        if not np.array_equal(la.selected, lb.selected):
            return (f"round {la.round}: selected {la.selected.tolist()} vs "
                    f"{lb.selected.tolist()}")
        for f in LOG_FIELDS:
            va, vb = getattr(la, f), getattr(lb, f)
            if va != vb:
                return f"round {la.round}: {f} {va!r} vs {vb!r}"
    return None


def record_rounds(srv: FederatedServer) -> list:
    """Keep, for every round ``srv`` trains, the cohort runtime's inputs
    (global params, winners, history) and the aggregate it returned, so
    that another runtime can train the same round from the same inputs."""
    rounds = []
    train = srv.runtime.train_cohort

    def recording(params, sel_idx, history):
        out = train(params, sel_idx, history)
        rounds.append((params, np.array(sel_idx), np.array(history), out))
        return out

    srv.runtime.train_cohort = recording
    return rounds


def compare_runs(check: Checks, name: str, a: FederatedServer,
                 b: FederatedServer, a_rounds: list) -> None:
    """``a`` and ``b`` ran the same config on two runtimes; ``a_rounds``
    is what :func:`record_rounds` kept of ``a``'s rounds.

    Logs and state must be identical.  Params are held to PARAM_TOL round
    by round: ``b``'s runtime trains each of ``a``'s rounds from the same
    params and winners.  The end-to-end params difference is printed but
    not held to it: local SGD through ReLU and max-pool kinks turns a
    1e-8 reassociation difference in round 1's aggregate into up to 1e-4
    in round 2's (a CPU run of this config on a forced 4-device mesh)."""
    div = first_log_divergence(a.logs, b.logs)
    check(div is None, f"{name}: selection/energy round logs identical"
          + ("" if div is None else f" (first divergence: {div})"))
    for field in ("history", "residual", "clusters"):
        va = np.asarray(getattr(a.state, field))
        vb = np.asarray(getattr(b.state, field))
        same = np.array_equal(va, vb)
        where = "" if same else \
            f" (first differing client: {int(np.flatnonzero(va != vb)[0])})"
        check(same, f"{name}: final state.{field} identical{where}")
    first_bad = None
    for t, (params, sel_idx, history, out) in enumerate(a_rounds):
        ref = b.runtime.train_cohort(params, sel_idx, history)
        diff, leaf = max_param_diff(out, ref)
        print(f"  round {t}: aggregate max |diff| {diff!r} (at {leaf}), "
              "both runtimes trained from the same params and winners")
        if first_bad is None and not diff < PARAM_TOL:
            first_bad = t
    check(len(a_rounds) == len(a.logs) and first_bad is None,
          f"{name}: every round's aggregate within {PARAM_TOL}"
          + ("" if first_bad is None else
             f" (first diverges at round {first_bad})"))
    diff, leaf = max_param_diff(a.params, b.params)
    print(f"  end to end after {len(a.logs)} rounds: params max |diff| "
          f"{diff!r} (at {leaf})")


def run_timed(srv: FederatedServer, **kw):
    t0 = time.perf_counter()
    logs = srv.run(**kw)
    return logs, time.perf_counter() - t0


def phase_paper(check: Checks, data):
    """Phases 2-4: the device run, stage 1 against the reference, and the
    sequential reference run."""
    cfg = paper_cfg()
    srv = make_server(cfg, data)
    params0 = srv.params
    # the key FederatedServer.cluster draws next (stage 1 runs first)
    stage1_key = jax.random.split(srv.key)[1]
    print(f"== paper trainer: runtime=device N={cfg.num_clients} "
          f"J={cfg.num_clusters} ratio={cfg.select_ratio} pool={POOL} "
          f"rounds={cfg.rounds}, sync audit on rounds 1..{cfg.rounds - 1}",
          flush=True)
    # audit_warm_rounds=1: warmup() compiles every class program before
    # round 0, so every round after the first is warm
    logs, wall = run_timed(srv, audit_sync=True, audit_warm_rounds=1)
    print(f"  device run: {wall:.1f} s wall (compile included)")
    for log in logs:
        print(f"  round {log.round}: winners={log.selected.size} "
              f"acc={log.test_acc!r} loss={log.test_loss!r} "
              f"energy_std={log.energy_std!r}")
    check(True, "sync audit: no implicit host transfer in the warm rounds")
    check(all(math.isfinite(l.test_loss) for l in logs),
          "test losses finite")
    check(logs[-1].test_acc > 0.1,
          f"final test accuracy {logs[-1].test_acc!r} above chance (0.1)")

    print("== stage 1: compiled Pallas lloyd_step vs impl='ref'", flush=True)
    feats = srv.runtime.cluster_features(params0, stage1_key, "gradient")
    labels, _, pfeats = CL.cluster_clients(
        srv.adapter.grad, params0, None, cfg, stage1_key,
        precomputed_feats=feats)
    kmeans = jax.jit(lambda f: CL.kmeans(f, cfg.num_clusters, stage1_key))
    hlo = kmeans.lower(pfeats).compile().as_text()
    check("tpu_custom_call" in hlo,
          f"stage-1 k-means on {tuple(pfeats.shape)} features compiles to "
          "a Pallas TPU kernel (tpu_custom_call)")
    check(np.array_equal(np.asarray(labels), np.asarray(srv.state.clusters)),
          "the server's stage-1 labels equal the kernel's on the same "
          "features")
    labels_ref, _ = CL.kmeans(pfeats, cfg.num_clusters, stage1_key,
                              impl="ref")
    lab, lab_r = np.asarray(labels), np.asarray(labels_ref)
    check(np.array_equal(lab, lab_r),
          f"kernel labels equal impl='ref' labels "
          f"({int((lab != lab_r).sum())} of {lab.size} differ)")
    print(f"  cluster sizes: {np.bincount(lab, minlength=cfg.num_clusters)}")

    print(f"== reference: {REF_ROUNDS} rounds on runtime=device and on "
          "runtime=sequential", flush=True)
    short = paper_cfg(rounds=REF_ROUNDS)
    dev = make_server(short, data)
    dev_rounds = record_rounds(dev)
    _, wall = run_timed(dev)
    print(f"  device run: {wall:.1f} s wall")
    div = first_log_divergence(logs[:REF_ROUNDS], dev.logs)
    check(div is None, f"the first {REF_ROUNDS} rounds repeat the "
          f"{cfg.rounds}-round run's logs"
          + ("" if div is None else f" (first divergence: {div})"))
    seq = make_server(short.replace(runtime="sequential"), data)
    _, wall = run_timed(seq)
    print(f"  sequential run: {wall:.1f} s wall")
    compare_runs(check, "device vs sequential", dev, seq, dev_rounds)


def phase_selection_scan(check: Checks):
    cfg = FLConfig(num_clients=SCAN_CLIENTS, num_clusters=SCAN_CLUSTERS,
                   select_ratio=0.1, rounds=SCAN_ROUNDS,
                   init_energy_mode="normal", seed=SEED)
    print(f"== selection-only scan: N={SCAN_CLIENTS} J={SCAN_CLUSTERS} "
          f"T={SCAN_ROUNDS}", flush=True)
    key = jax.random.PRNGKey(SEED)
    state = R.synthetic_fleet(cfg, key)
    kr = jax.random.fold_in(key, 1)
    t0 = time.perf_counter()
    _, metrics, _ = R.simulate_rounds(state, cfg, kr, SCAN_ROUNDS)
    metrics = jax.device_get(metrics)
    cold = time.perf_counter() - t0
    t1 = time.perf_counter()
    final, again, _ = R.simulate_rounds(state, cfg, kr, SCAN_ROUNDS)
    jax.block_until_ready((final, again))
    warm = time.perf_counter() - t1
    again = jax.device_get(again)
    print(f"  smoke reading, not a benchmark: warm "
          f"{SCAN_ROUNDS / warm!r} rounds/s, compile ~{cold - warm!r} s "
          f"(cold call {cold!r} s minus warm call {warm!r} s)")
    check(all(np.isfinite(np.asarray(v, np.float64)).all()
              for v in metrics.values()), "scan metrics finite")
    check(bool((np.asarray(metrics["num_winners"]) > 0).all()),
          "every round has winners "
          f"(mean {float(np.mean(metrics['num_winners']))!r})")
    check(all(np.array_equal(metrics[k], again[k]) for k in metrics),
          "the warm call repeats the cold call's metrics exactly")


def phase_four_chips(check: Checks, data):
    cfg = paper_cfg(rounds=REF_ROUNDS)
    print(f"== four-chip cohort mesh: runtime=device on data={4} vs "
          "cohort_mesh_devices=1", flush=True)
    mesh = make_server(cfg, data)
    one = make_server(cfg.replace(cohort_mesh_devices=1), data)
    mesh_rounds = record_rounds(mesh)
    check(mesh.runtime.engine.data_axis_size == 4,
          f"mesh run's data axis is {mesh.runtime.engine.data_axis_size}")
    check(one.runtime.engine.data_axis_size == 1,
          f"reference run's data axis is "
          f"{one.runtime.engine.data_axis_size}")
    _, wall = run_timed(mesh, audit_sync=True, audit_warm_rounds=1)
    print(f"  4-chip run: {wall:.1f} s wall (compile included)")
    _, wall = run_timed(one)
    print(f"  1-chip run: {wall:.1f} s wall (compile included)")
    compare_runs(check, "4-chip mesh vs 1 chip", mesh, one, mesh_rounds)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip cohort mesh path and "
                         "the one-chip run it is compared with")
    args = ap.parse_args()

    cache_dir = use_compile_cache(ROOT)
    cache = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devs)}", file=sys.stderr)
        return 2

    check = Checks()
    t0 = time.perf_counter()
    data = make_image_dataset("mnist", n_train=POOL, n_test=POOL // 6,
                              seed=SEED)
    print(f"data: {POOL} train images in {time.perf_counter() - t0:.1f} s")
    if args.chips == 4:
        phase_four_chips(check, data)
    else:
        phase_paper(check, data)
        phase_selection_scan(check)
    print(f"compile cache: {cache_dir} hits={cache['hits']} "
          f"misses={cache['misses']}; total {time.perf_counter() - t0:.1f} s")
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed:",
              file=sys.stderr)
        for what in check.failed:
            print(f"  {what}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
