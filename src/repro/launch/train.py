"""FL training driver.

Three modes:

  * ``--mode paper`` (default): the paper-faithful simulation — N edge
    clients with CNNs on a synthetic non-IID/imbalanced image dataset,
    gradient clustering + per-cluster auction selection, FedAvg/FedProx
    aggregation, energy accounting. This reproduces the paper's Figs 4-10.

  * ``--mode transformer``: FL over a registry architecture (reduced config
    on CPU; the full configs are exercised by the dry-run). Clients hold
    topic-conditional token shards; one FL round = selection -> local LM
    steps -> weighted aggregation.

  * ``--mode selection``: selection-only simulation — the full per-round
    auction/energy dynamics (cost, Nash bids, s_min, per-cluster reverse
    auction, rewards, energy/history) WITHOUT stage-3 training, run as one
    lax.scan-over-rounds compiled program (repro.core.rounds.simulate_rounds)
    over a synthetic fleet. This is the Fig 9/10-style experiment engine at
    scale: N=100k-1M clients x thousands of rounds on a laptop.

Cohort execution backend (``--runtime``, see repro/sim/):

  * ``sequential`` (default): the reference oracle — each winner trains
    in its own Python loop of jitted steps.
  * ``vectorized``: whole-cohort execution — winners are packed into
    padded, size-bucketed ``(C, steps, bs, ...)`` tensors and their local
    epochs run as one compiled vmap/scan program per bucket, with the
    weighted FedAvg aggregation fused in.  Results match ``sequential``
    up to float reassociation (same shuffles, same batch boundaries).
    Caveat: clients are bucketed by (batch size, pow2 step band) and
    padded to the bucket's max step count, so uneven cohorts pay up to
    ~2x the smallest member's steps within a band; jit retraces per
    bucket shape (padding rounds the client axis to a multiple of the
    vmap chunk width and steps to a multiple of 4 to bound the cache).
  * ``sharded``: the vectorized engine mesh-mapped across the cohort
    mesh (``--cohort-devices``, default all local devices): each
    bucket's client axis is shard_map'd over the mesh's ``data`` axis
    with replicated params and an on-mesh psum FedAvg reduction.  On a
    1-device host it degrades to the debug mesh (same program); to try
    a multi-device CPU mesh set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` BEFORE
    launching (the flag must precede first jax init — see
    launch/mesh.py).  Equivalence with ``vectorized`` (and the oracle)
    is enforced by tests/test_sim.py on both mesh shapes.
  * ``device``: the device-resident fleet pipeline (repro.sim.fleet) —
    all clients' data packed once into static capacity-class device
    tensors at init, per-round cohorts assembled as on-device gathers,
    compile-once shape policy (zero retraces after warm-up), async
    round loop.  ``--eval-every N`` evaluates test accuracy/loss only
    every N rounds (skipped rounds log NaN; the final round always
    evaluates) — eval is the deepest per-round host sync, so raising it
    lengthens the async pipeline for every runtime.

Usage:
  PYTHONPATH=src python -m repro.launch.train --mode paper \
      --scheme gradient_cluster_auction --rounds 30
  PYTHONPATH=src python -m repro.launch.train --mode paper \
      --runtime vectorized --clients 200 --rounds 30
  PYTHONPATH=src python -m repro.launch.train --mode paper \
      --runtime device --eval-every 5 --rounds 30
  PYTHONPATH=src python -m repro.launch.train --mode transformer \
      --arch qwen2-0.5b --rounds 3
  PYTHONPATH=src python -m repro.launch.train --mode selection \
      --clients 1000000 --clusters 100 --rounds 1000
  PYTHONPATH=src python -m repro.launch.train --mode paper \
      --runtime device --rounds 30 --log-jsonl runs/events.jsonl \
      --audit-sync            # structured telemetry + sync audit
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro import obs
from repro.configs.base import FLConfig
from repro.core.adapters import cnn_adapter, transformer_adapter
from repro.core.server import FederatedServer
from repro.data.partition import partition_clients
from repro.data.synthetic import make_image_dataset, make_token_dataset
from repro.launch.compile_cache import use_compile_cache


def run_paper(args) -> dict:
    cfg = FLConfig(
        num_clients=args.clients, num_clusters=args.clusters,
        select_ratio=args.select_ratio, rounds=args.rounds,
        local_epochs=args.local_epochs, lr=args.lr,
        non_iid_level=args.nu, scheme=args.scheme,
        scheme_select=args.scheme_select,
        fedcs_deadline=args.fedcs_deadline,
        aggregator=args.aggregator, init_energy_mode=args.energy_mode,
        runtime=args.runtime, cohort_mesh_devices=args.cohort_devices,
        eval_every=args.eval_every, seed=args.seed,
        churn=args.churn, deadline=args.deadline,
        straggler_profile=args.straggler_profile,
        aggregation=args.aggregation, buffer_goal=args.buffer_goal,
        buffer_timeout=args.buffer_timeout,
        adversary_frac=args.adversary_frac, attack=args.attack,
        attack_scale=args.attack_scale, defense=args.defense,
        defense_mode=args.defense_mode,
        reputation_mode=args.reputation_mode,
        watchdog=args.watchdog, watchdog_ring=args.watchdog_ring)
    train, test = make_image_dataset(args.dataset,
                                     n_train=args.pool, n_test=args.pool // 6,
                                     seed=args.seed)
    clients = partition_clients(train.y, cfg, seed=args.seed)
    adapter = cnn_adapter(args.dataset)
    ntest = min(1000, len(test.x))
    srv = FederatedServer(cfg, adapter, train.x, train.y, clients,
                          {"x": test.x[:ntest], "y": test.y[:ntest]})
    t0 = time.time()
    logs = srv.run(verbose=not args.quiet, audit_sync=args.audit_sync,
                   checkpoint_every=args.checkpoint_every,
                   checkpoint_path=args.checkpoint_path,
                   resume=args.resume)
    out = {
        "mode": "paper", "scheme": args.scheme,
        "scheme_select": args.scheme_select, "nu": args.nu,
        "aggregator": args.aggregator, "dataset": args.dataset,
        "runtime": args.runtime,
        "rounds": [l.round for l in logs],
        "test_acc": [l.test_acc for l in logs],
        "test_loss": [l.test_loss for l in logs],
        "energy_std": [l.energy_std for l in logs],
        "mean_bid": [l.mean_bid for l in logs],
        "server_reward": [l.server_reward for l in logs],
        "client_reward_sum": [l.client_reward_sum for l in logs],
        "vds_gap": [l.vds_gap for l in logs],
        "wall_s": time.time() - t0,
    }
    if srv.dynamics:
        from repro.sim import dynamics as DYN
        codes = (np.concatenate(srv.outcome_log) if srv.outcome_log
                 else np.zeros((0,), np.int32))
        out["dynamics"] = {
            "churn": cfg.churn, "deadline": cfg.deadline,
            "aggregation": cfg.aggregation,
            "num_completed": int((codes == DYN.COMPLETED).sum()),
            "num_late": int((codes == DYN.LATE).sum()),
            "num_dropped": int((codes == DYN.DROPPED).sum()),
        }
    if srv.defended:
        out["defense"] = {
            "attack": cfg.attack, "adversary_frac": cfg.adversary_frac,
            "defense": cfg.defense, "defense_mode": cfg.defense_mode,
            "reputation_mode": cfg.reputation_mode,
            "num_adversaries": int(srv._adv_mask.sum()),
            "num_quarantined": srv.defense_totals["quarantined"],
            "num_screened": srv.defense_totals["screened"],
            "num_banned_final": srv.defense_totals["banned_final"],
        }
    if srv.cfg.watchdog_enabled:
        out["watchdog"] = {
            "ring": cfg.watchdog_ring,
            "rollbacks": srv.watchdog_totals["rollbacks"],
            "snapshots": srv.watchdog_totals["snapshots"],
        }
    return out


def run_transformer(args) -> dict:
    from repro.configs.registry import get_smoke_config
    mcfg = get_smoke_config(args.arch)
    cfg = FLConfig(
        num_clients=max(10, args.clients // 5), num_clusters=5,
        select_ratio=0.2, rounds=args.rounds, lr=args.lr,
        non_iid_level=args.nu, scheme=args.scheme, num_classes=10,
        scheme_select=args.scheme_select,
        fedcs_deadline=args.fedcs_deadline,
        sample_window=8, cluster_resamples=2, runtime=args.runtime,
        cohort_mesh_devices=args.cohort_devices,
        eval_every=args.eval_every, seed=args.seed,
        churn=args.churn, deadline=args.deadline,
        straggler_profile=args.straggler_profile,
        aggregation=args.aggregation, buffer_goal=args.buffer_goal,
        buffer_timeout=args.buffer_timeout)
    toks, topics = make_token_dataset(
        num_topics=10, vocab=mcfg.vocab_size, seq_len=32,
        n=cfg.num_clients * 40, seed=args.seed)
    clients = partition_clients(topics, cfg, seed=args.seed)
    adapter = transformer_adapter(mcfg)
    test_n = min(64, len(toks))
    srv = FederatedServer(cfg, adapter, toks, topics, clients,
                          {"x": toks[:test_n], "y": topics[:test_n]})
    t0 = time.time()
    logs = srv.run(verbose=not args.quiet, audit_sync=args.audit_sync)
    return {
        "mode": "transformer", "arch": args.arch, "scheme": args.scheme,
        "scheme_select": args.scheme_select, "runtime": args.runtime,
        "rounds": [l.round for l in logs],
        "test_loss": [l.test_loss for l in logs],
        "test_acc": [l.test_acc for l in logs],
        "energy_std": [l.energy_std for l in logs],
        "wall_s": time.time() - t0,
    }


def run_selection(args) -> dict:
    """Selection-only round dynamics at scale: one compiled scan over all
    rounds, metrics buffered on device and fetched once at the end."""
    import jax.numpy as jnp

    from repro.core import rounds as R
    cfg = FLConfig(
        num_clients=args.clients, num_clusters=args.clusters,
        select_ratio=args.select_ratio, rounds=args.rounds,
        scheme=args.scheme, scheme_select=args.scheme_select,
        fedcs_deadline=args.fedcs_deadline,
        init_energy_mode=args.energy_mode,
        seed=args.seed)
    key = jax.random.PRNGKey(args.seed)
    state = R.synthetic_fleet(cfg, key)
    kr = jax.random.fold_in(key, 1)
    # cold call = compile + run; a second identical call hits the jit
    # cache, so its wall clock is the warm throughput — reporting
    # rounds_per_s off the cold call buried the actual per-round rate
    # under one-time compile time (at small T compile dominates).  The
    # re-run doubles the simulation cost, so huge sweeps (1M clients x
    # 1000s of rounds) can opt out with --no-warm-rerun and take the
    # compile-inclusive rate instead.
    t0 = time.time()
    with obs.span("selection/cold", rounds=args.rounds,
                  clients=args.clients):
        final, metrics, _ = R.simulate_rounds(state, cfg, kr, args.rounds)
        metrics = obs.device_get(metrics)  # ONE host transfer for T rounds
    cold = time.time() - t0
    if args.no_warm_rerun:
        warm, compile_s = cold, None
    else:
        t1 = time.time()
        with obs.span("selection/warm", rounds=args.rounds):
            final, m2, _ = R.simulate_rounds(state, cfg, kr, args.rounds)
            jax.block_until_ready((final, m2))
        warm = time.time() - t1
        compile_s = max(cold - warm, 0.0)
    out = {
        "mode": "selection", "scheme": args.scheme,
        "scheme_select": args.scheme_select,
        "clients": args.clients, "clusters": args.clusters,
        "rounds": list(range(args.rounds)),
        "energy_std": [float(v) for v in metrics["energy_std"]],
        "mean_bid": [float(v) for v in metrics["mean_bid"]],
        "server_reward": [float(v) for v in metrics["server_reward"]],
        "client_reward_sum": [float(v)
                              for v in metrics["client_reward_sum"]],
        "num_winners": [int(v) for v in metrics["num_winners"]],
        "final_energy_mean": float(jnp.mean(final.residual)),
        "rounds_per_s": args.rounds / warm,
        "compile_s": compile_s,
        # wall_s keeps its pre-PR-4 meaning: ONE simulation incl. compile
        # (the warm timing re-run is excluded)
        "wall_s": cold,
    }
    # mirror the fetched metric columns into the obs round series (host
    # floats already in hand — no extra device traffic)
    if obs.OBS.enabled:
        for t in range(args.rounds):
            obs.OBS.record_round(
                t, energy_std=out["energy_std"][t],
                mean_bid=out["mean_bid"][t],
                server_reward=out["server_reward"][t],
                client_reward_sum=out["client_reward_sum"][t],
                num_winners=out["num_winners"][t],
                fairness_hist_std=float(metrics["fairness_hist_std"][t]),
                **{k: float(metrics[k][t]) for k in
                   ("budget_spent", "budget_remaining", "budget_queue")
                   if k in metrics})
        obs.flush()
    timing = "incl. compile" if compile_s is None \
        else f"warm; compile={compile_s:.2f}s"
    obs.log(f"selection-only: N={args.clients} T={args.rounds} "
            f"{out['rounds_per_s']:.1f} rounds/s ({timing}) "
            f"final_energy_std={out['energy_std'][-1]:.3f}", always=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="paper",
                    choices=["paper", "transformer", "selection"])
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--dataset", default="mnist",
                    choices=["mnist", "fmnist", "cifar"])
    ap.add_argument("--scheme", default="gradient_cluster_auction")
    ap.add_argument("--scheme-select", default="paper",
                    choices=["paper", "random", "fedcs",
                             "longterm_auction"],
                    help="control-plane selection scheme "
                         "(repro.core.schemes registry): 'paper' is the "
                         "pre-registry control plane (dispatching on "
                         "--scheme, bit-identical traces); 'random' picks "
                         "uniformly per cluster among available clients; "
                         "'fedcs' gates auction entry on predicted "
                         "latency meeting the deadline (arXiv:1804.08333)"
                         "; 'longterm_auction' carries a budget/payment "
                         "ledger across rounds (arXiv:2508.09181)")
    ap.add_argument("--fedcs-deadline", type=float, default=1.5,
                    help="fedcs: bid-time feasibility bound in fleet-mean "
                         "round times, used when --deadline is 0 (a "
                         "positive --deadline takes precedence so the "
                         "auction gates on the enforced deadline)")
    ap.add_argument("--aggregator", default="fedavg",
                    choices=["fedavg", "fedprox"])
    ap.add_argument("--runtime", default="sequential",
                    choices=["sequential", "vectorized", "sharded",
                             "device"],
                    help="cohort execution backend (repro.sim): "
                         "'vectorized' runs whole cohorts as one compiled "
                         "vmap/scan program per size bucket; 'sharded' "
                         "additionally maps the client axis over the "
                         "cohort mesh's data axis (shard_map + psum); "
                         "'device' keeps the fleet's data resident on "
                         "device in static capacity classes (compile "
                         "once, zero per-round host repack)")
    ap.add_argument("--cohort-devices", type=int, default=0,
                    help="data-axis size of the cohort mesh for "
                         "--runtime sharded/device (0 = all local "
                         "devices)")
    ap.add_argument("--eval-every", type=int, default=1,
                    help="evaluate test acc/loss every N rounds (skipped "
                         "rounds log NaN; the final round always "
                         "evaluates) — deepens the async round pipeline")
    ap.add_argument("--clients", type=int, default=100)
    ap.add_argument("--clusters", type=int, default=10)
    ap.add_argument("--select-ratio", type=float, default=0.1)
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--local-epochs", type=int, default=1)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--nu", type=float, default=1.0)
    ap.add_argument("--pool", type=int, default=12000)
    ap.add_argument("--energy-mode", default="normal",
                    choices=["full", "normal"])
    ap.add_argument("--churn", type=float, default=0.0,
                    help="fleet dynamics: per-round dropout probability "
                         "of the availability churn process (0 disables "
                         "— runs stay bit-identical to the dynamics-free "
                         "path under the same seed)")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="fleet dynamics: FedCS-style round deadline in "
                         "units of the fleet-mean round time; a winner "
                         "whose sampled latency exceeds it is LATE "
                         "(0 disables deadline misses)")
    ap.add_argument("--straggler-profile", default="energy",
                    choices=["energy", "uniform", "lognormal", "none"],
                    help="latency heterogeneity for the straggler model: "
                         "'energy' ties slowdown to residual battery "
                         "(the paper's heterogeneity profile), "
                         "'uniform'/'lognormal' are energy-independent, "
                         "'none' is deterministic")
    ap.add_argument("--aggregation", default="sync",
                    choices=["sync", "buffered"],
                    help="'sync' re-weights FedAvg over the surviving "
                         "cohort; 'buffered' additionally folds LATE "
                         "winners' updates in FedBuff-style with "
                         "staleness-discounted weights at goal-count or "
                         "timeout boundaries")
    ap.add_argument("--buffer-goal", type=int, default=4,
                    help="buffered aggregation: fold once this many late "
                         "updates have arrived")
    ap.add_argument("--buffer-timeout", type=int, default=4,
                    help="buffered aggregation: fold once the oldest "
                         "arrived update is this many rounds stale")
    ap.add_argument("--adversary-frac", type=float, default=0.0,
                    help="Byzantine robustness: fraction of the fleet "
                         "corrupting its update after local training "
                         "(seed-deterministic population; 0 disables — "
                         "runs stay bit-identical to the attack-free "
                         "path)")
    ap.add_argument("--attack", default="none",
                    choices=["none", "nan", "scale", "signflip", "noise",
                             "sub_clip", "alie", "on_off"],
                    help="corruption model applied to adversarial "
                         "winners' param deltas: 'nan' poisons, 'scale' "
                         "amplifies, 'signflip' amplifies and negates, "
                         "'noise' adds gaussian noise at attack-scale x "
                         "the cohort RMS delta; ADAPTIVE attacks observe "
                         "the defense: 'sub_clip' pushes against the "
                         "honest mean at a norm just under the clip "
                         "threshold, 'alie' colludes on mean - z*std "
                         "(inside the trimmed band), 'on_off' alternates "
                         "clean/dirty phases to farm reputation")
    ap.add_argument("--attack-scale", type=float, default=25.0,
                    help="attack magnitude multiplier (scale/signflip/"
                         "noise/on_off)")
    ap.add_argument("--defense", default="none",
                    choices=["none", "clip", "trimmed", "median"],
                    help="screened robust aggregation "
                         "(repro.core.aggregation): non-finite updates "
                         "are always quarantined (and strike the "
                         "sender's auction reputation), then 'clip' "
                         "norm-clips to a running-median threshold, "
                         "'trimmed'/'median' aggregate coordinate-wise; "
                         "'none' is the undefended FedAvg baseline")
    ap.add_argument("--defense-mode", default="static",
                    choices=["static", "adaptive"],
                    help="'adaptive' auto-tunes the screen: survivor "
                         "norms outside a running median + k*MAD band "
                         "are excluded and fractionally struck, with k "
                         "tightening under attack pressure (rejection-"
                         "rate EMA) and relaxing when it falls; 'static' "
                         "is PR 8's fixed-threshold behavior")
    ap.add_argument("--reputation-mode", default="ban",
                    choices=["ban", "price"],
                    help="'ban' hard-excludes clients at or above the "
                         "strike threshold (bit-identical to the "
                         "original behavior); 'price' multiplies "
                         "(1 + gain*strikes) into the effective bid at "
                         "the auction ranking step — tainted clients "
                         "must underbid to win, payment stays on the "
                         "true bid")
    ap.add_argument("--watchdog", default="off", choices=["off", "on"],
                    help="divergence watchdog: keep a ring of healthy "
                         "snapshots, detect non-finite/spiking evals, "
                         "roll back to the newest healthy snapshot, "
                         "tighten the defense and decay the server LR "
                         "(every rollback is a 'watchdog' obs event)")
    ap.add_argument("--watchdog-ring", type=int, default=3,
                    help="watchdog: number of healthy snapshots kept in "
                         "the rollback ring")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="snapshot server params + selection/defense "
                         "state every N rounds (0 disables)")
    ap.add_argument("--checkpoint-path", default=None, metavar="PATH",
                    help="checkpoint file stem (.npz + .json manifest)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from --checkpoint-path if it exists "
                         "(skips stage-1 clustering; dynamics-free runs "
                         "continue bit-identically)")
    ap.add_argument("--no-warm-rerun", action="store_true",
                    help="selection mode: skip the second (warm) timing "
                         "run — rounds_per_s then includes compile time "
                         "(use for huge N x T sweeps where doubling the "
                         "simulation cost is not worth the clean number)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quiet", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--log-jsonl", default=None, metavar="PATH",
                    help="write the structured obs event stream (round "
                         "series, spans, jax counters) as JSON lines; "
                         "validate with `python -m repro.obs.schema`")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the whole run "
                         "for TensorBoard/Perfetto")
    ap.add_argument("--audit-sync", action="store_true",
                    help="paper/transformer: wrap warm round dispatches "
                         "in the transfer-guard sync auditor — any "
                         "implicit host transfer in the round loop "
                         "raises at the offending op")
    args = ap.parse_args()

    use_compile_cache()
    obs.configure(jsonl=args.log_jsonl, quiet=args.quiet)
    with obs.maybe_profile(args.profile_dir):
        result = {"paper": run_paper, "transformer": run_transformer,
                  "selection": run_selection}[args.mode](args)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
        obs.log(f"wrote {args.out}", always=True)
    if result.get("test_acc"):
        obs.log(f"final acc={result['test_acc'][-1]:.3f} "
                f"energy_std={result['energy_std'][-1]:.3f} "
                f"wall={result['wall_s']:.0f}s", always=True)
    if "defense" in result:
        d = result["defense"]
        obs.log(f"defense {d['defense']!r} ({d['defense_mode']}, "
                f"reputation={d['reputation_mode']}) vs attack "
                f"{d['attack']!r}: adversaries={d['num_adversaries']} "
                f"quarantined={d['num_quarantined']} "
                f"screened={d['num_screened']} "
                f"banned={d['num_banned_final']}", always=True)
    if "watchdog" in result:
        w = result["watchdog"]
        obs.log(f"watchdog: rollbacks={w['rollbacks']} "
                f"snapshots={w['snapshots']} (ring={w['ring']})",
                always=True)
    obs.flush()


if __name__ == "__main__":
    main()
