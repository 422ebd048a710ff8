"""Benchmark harness — one benchmark per paper table/figure plus kernel and
selection micro-benchmarks. Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run            # standard pass
  PYTHONPATH=src python -m benchmarks.run --quick    # CI-sized
  PYTHONPATH=src python -m benchmarks.run --only fig6
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

RESULTS = os.path.join(os.path.dirname(__file__), "results")
REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _t(fn, n=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.time()
    for _ in range(n):
        jax.block_until_ready(fn())
    return (time.time() - t0) / n * 1e6  # us


def _row(name, us, derived):
    print(f"{name},{us:.1f},{derived}", flush=True)


def _save(name, obj):
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, f"{name}.json"), "w") as f:
        json.dump(obj, f, indent=1)


def _git_commit() -> str:
    import subprocess
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=REPO_ROOT,
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _summary(name, **headline):
    """Write the top-level ``BENCH_<name>.json`` perf-trajectory summary:
    the benchmark's headline numbers stamped with wall time + commit, so
    ``git log -p BENCH_fleet_dynamics.json`` IS the perf history."""
    rec = {"bench": name, "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
           "commit": _git_commit(), **headline}
    path = os.path.join(REPO_ROOT, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
        f.write("\n")


# ----------------------------------------------------------------------
# micro: kernels
# ----------------------------------------------------------------------

def bench_kernels(quick: bool):
    from repro.kernels import ops, ref
    key = jax.random.PRNGKey(0)
    n, f, k = (512, 128, 10) if quick else (4096, 256, 10)
    x = jax.random.normal(key, (n, f))
    c = jax.random.normal(jax.random.fold_in(key, 1), (k, f))
    us_ref = _t(lambda: ref.kmeans_assign_ref(x, c))
    lab_p = ops.kmeans_assign(x, c, impl="pallas")
    us_pal = _t(lambda: ops.kmeans_assign(x, c, impl="pallas"))
    match = bool((lab_p == ref.kmeans_assign_ref(x, c)).all())
    _row("kmeans_assign_ref", us_ref, f"N={n} F={f} K={k}")
    _row("kmeans_assign_pallas", us_pal, f"match={match}")

    from repro.models.layers import chunked_attention, naive_attention
    B, S, H, hd = (1, 512, 4, 64) if quick else (2, 2048, 8, 64)
    q, kk, v = (jax.random.normal(jax.random.fold_in(key, i), (B, S, H, hd))
                for i in range(3))
    fa = jax.jit(lambda q, k, v: chunked_attention(q, k, v, causal=True))
    na = jax.jit(lambda q, k, v: naive_attention(q, k, v, causal=True))
    us_f = _t(lambda: fa(q, kk, v))
    us_n = _t(lambda: na(q, kk, v))
    err = float(jnp.max(jnp.abs(fa(q, kk, v) - na(q, kk, v))))
    _row("flash_attention_jnp", us_f, f"S={S} err_vs_naive={err:.1e}")
    _row("naive_attention", us_n, f"S={S}")


# ----------------------------------------------------------------------
# micro: stage-1 clustering engine
# ----------------------------------------------------------------------

def bench_clustering(quick: bool):
    """Fused jitted k-means engine (batched restarts + incremental ++ +
    fused assign/update) vs the seed implementation (Python restart loop,
    (N,K,F)-broadcast seeding, assign_ref) across an N sweep. The seed
    baseline is skipped above 20k clients — its seeding alone materializes
    an N*K*F float buffer per centroid pick (1 GB at N=100k)."""
    from repro.core import clustering as CL
    ns = [512, 2048] if quick else [2048, 10_000, 50_000, 100_000]
    f, k = 256, 10
    ref_cap = 2048 if quick else 20_000
    rng = np.random.default_rng(0)
    centers = rng.normal(size=(k, f)) * 8.0
    key = jax.random.PRNGKey(0)
    out = {}
    for n in ns:
        sizes = [n // k + (1 if i < n % k else 0) for i in range(k)]
        x = jnp.asarray(np.concatenate(
            [c + rng.normal(size=(s, f)) for c, s in zip(centers, sizes)]),
            jnp.float32)
        assert x.shape[0] == n
        lab_new, _ = jax.block_until_ready(CL.kmeans(x, k, key))  # warmup
        us_new = _t(lambda: CL.kmeans(x, k, key), n=3, warmup=0)
        row = {"fused_us": us_new, "N": n, "F": f, "K": k}
        derived = ""
        if n <= ref_cap:
            # one eager reference run doubles as warmup and label source
            lab_ref, _ = jax.block_until_ready(
                CL.kmeans_reference(x, k, key))
            us_ref = _t(lambda: CL.kmeans_reference(x, k, key),
                        n=1, warmup=0)
            agree = float((np.asarray(lab_new) == np.asarray(lab_ref))
                          .mean())
            row.update(reference_us=us_ref, speedup=us_ref / us_new,
                       label_agreement=agree)
            _row(f"kmeans_reference_N{n}", us_ref, f"F={f} K={k}")
            derived = (f"speedup={us_ref / us_new:.1f}x "
                       f"label_agreement={agree:.3f}")
        _row(f"kmeans_fused_N{n}", us_new, derived)
        out[n] = row
    _save("clustering", out)
    top = out[max(out)]
    _summary("clustering", N=top["N"], fused_us=top["fused_us"],
             speedup=top.get("speedup"))


# ----------------------------------------------------------------------
# micro: selection / auction throughput
# ----------------------------------------------------------------------

def bench_selection(quick: bool):
    """Fused round control plane (repro.core.rounds.simulate_rounds — one
    lax.scan over T rounds of the full auction/energy dynamics, metrics
    buffered on device) vs the seed per-round Python path (eager
    select/reward/update with a host metric fetch every round) across an
    N sweep. The reference is capped: its per-round dispatch+sync
    overhead dominates long before N=1M; the fused path alone sweeps to
    a million clients."""
    from repro.configs.base import FLConfig
    from repro.core import rounds as R
    ns = [1000, 10_000] if quick else [10_000, 100_000, 1_000_000]
    ref_cap = 10_000 if quick else 100_000
    out = {}
    for n in ns:
        T = 16 if quick else (64 if n <= 100_000 else 16)
        cfg = FLConfig(num_clients=n, num_clusters=10, select_ratio=0.1,
                       scheme="gradient_cluster_auction",
                       init_energy_mode="normal")
        key = jax.random.PRNGKey(0)
        state = R.synthetic_fleet(cfg, key)
        kr = jax.random.fold_in(key, 1)

        def fused():
            fs, m, _ = R.simulate_rounds(state, cfg, kr, T)
            return m["energy_std"]

        # time the cold (compile+run) call separately so the reported
        # rounds/s is the warm throughput and compile cost is its own row
        t0 = time.time()
        jax.block_until_ready(fused())
        cold_s = time.time() - t0
        us_f = _t(fused, n=2 if n >= 1_000_000 else 3, warmup=0)
        compile_s = max(cold_s - us_f / 1e6, 0.0)
        fused_rps = T / (us_f / 1e6)
        row = {"N": n, "T": T, "fused_us_per_round": us_f / T,
               "fused_rounds_per_s": fused_rps, "compile_s": compile_s}
        derived = f"T={T} rounds_per_s={fused_rps:.1f} " \
                  f"compile_s={compile_s:.2f}"
        if n <= ref_cap:
            us_r = _t(lambda: R.simulate_rounds_reference(
                state, cfg, kr, T)[1]["energy_std"], n=1, warmup=1)
            ref_rps = T / (us_r / 1e6)
            row.update(ref_us_per_round=us_r / T,
                       ref_rounds_per_s=ref_rps,
                       speedup=us_r / us_f)
            _row(f"selection_rounds_ref_N{n}", us_r / T,
                 f"T={T} rounds_per_s={ref_rps:.1f}")
            derived += f" speedup={us_r / us_f:.1f}x"
        _row(f"selection_rounds_fused_N{n}", us_f / T, derived)
        out[n] = row
    _save("selection", out)
    top = out[max(out)]
    _summary("selection", N=top["N"], T=top["T"],
             warm_rounds_per_s=top["fused_rounds_per_s"],
             compile_s=top["compile_s"], speedup=top.get("speedup"))


def bench_fleet_dynamics(quick: bool):
    """Fleet-dynamics overhead + robustness: warm FL rounds/sec and test
    accuracy at dropout rates 0 / 0.1 / 0.3 (deadline + buffered
    aggregation on for the faulty fleets).  The rate-0 row runs the
    dynamics-free bit-exact path, so the delta to rate>0 rows is the
    full price of the fault model (fused fault step + outcome fetch +
    replacement sampling + buffer folds)."""
    from repro.configs.base import FLConfig
    from repro.core.adapters import cnn_adapter
    from repro.core.server import FederatedServer
    from repro.data.partition import partition_clients
    from repro.data.synthetic import make_image_dataset

    nclients = 24 if quick else 64
    warm_rounds, timed_rounds = (2, 4) if quick else (3, 8)
    base = FLConfig(num_clients=nclients, num_clusters=4,
                    select_ratio=10 / nclients if quick else 0.25,
                    local_epochs=2, scheme="gradient_cluster_auction",
                    sample_window=20, cluster_resamples=2,
                    init_energy_mode="normal", eval_every=10 ** 6,
                    runtime="device", seed=0)
    train, test = make_image_dataset("mnist", n_train=nclients * 130,
                                     n_test=256, seed=0)
    adapter = cnn_adapter("mnist")
    out = {"clients": nclients, "warm_rounds": warm_rounds,
           "timed_rounds": timed_rounds, "rates": {}}
    for rate in (0.0, 0.1, 0.3):
        cfg = base.replace(
            churn=rate, deadline=1.5 if rate > 0 else 0.0,
            aggregation="buffered" if rate > 0 else "sync")
        clients = partition_clients(train.y, cfg, seed=0)
        srv = FederatedServer(cfg, adapter, train.x, train.y, clients,
                              {"x": test.x[:256], "y": test.y[:256]})
        srv.run(rounds=warm_rounds)
        jax.block_until_ready(srv.params)
        t0 = time.time()
        for t in range(warm_rounds, warm_rounds + timed_rounds):
            srv._dispatch_round(t, eval_now=False)
        srv._flush_pending()
        jax.block_until_ready(srv.params)
        wall = time.time() - t0
        acc, _ = jax.device_get(srv._eval_step(srv.params, srv._test_dev))
        codes = (np.concatenate(srv.outcome_log) if srv.dynamics
                 else np.zeros((0,), np.int32))
        row = {
            "rounds_per_s": timed_rounds / wall,
            "test_acc": float(acc),
            "num_late": int((codes == 2).sum()),
            "num_dropped": int((codes == 3).sum()),
        }
        out["rates"][str(rate)] = row
        _row(f"fleet_dynamics_p{rate}", wall / timed_rounds * 1e6,
             f"rounds_per_s={row['rounds_per_s']:.2f} "
             f"acc={row['test_acc']:.3f} late={row['num_late']} "
             f"dropped={row['num_dropped']}")
    base_rps = out["rates"]["0.0"]["rounds_per_s"]
    out["overhead_p0.3"] = base_rps / out["rates"]["0.3"]["rounds_per_s"]
    _save("fleet_dynamics", out)
    _summary("fleet_dynamics", clients=nclients,
             rounds_per_s_p0=base_rps,
             rounds_per_s_p01=out["rates"]["0.1"]["rounds_per_s"],
             rounds_per_s_p03=out["rates"]["0.3"]["rounds_per_s"],
             acc_p0=out["rates"]["0.0"]["test_acc"],
             acc_p01=out["rates"]["0.1"]["test_acc"],
             acc_p03=out["rates"]["0.3"]["test_acc"],
             overhead_p03=out["overhead_p0.3"])


def bench_scheme_zoo(quick: bool):
    """Scheme x Non-IID benchmark matrix over the pluggable round
    control plane (repro.core.schemes): every registered selection
    scheme runs the SAME fused round programs on the device runtime, so
    the cells differ only in who gets selected.  Per cell: warm FL
    rounds/sec (the scheme dispatch must not cost throughput — every
    scheme compiles into the one lax.scan/step program), final test
    accuracy (convergence), final residual-energy std (the paper's
    energy-balance fairness, Fig 9/10) and the participation-history
    std (selection fairness).  The long-term auction additionally
    reports its budget ledger (total spend vs the Rg cap)."""
    from repro.configs.base import FLConfig
    from repro.core.adapters import cnn_adapter
    from repro.core.server import FederatedServer
    from repro.data.partition import partition_clients
    from repro.data.synthetic import make_image_dataset

    zoo = ("paper", "random", "fedcs", "longterm_auction")
    nclients = 24 if quick else 50
    warm_rounds, timed_rounds = (2, 4) if quick else (3, 8)
    rounds = 6 if quick else 30
    nus = (1.0,) if quick else (1.0, 0.5)
    base = FLConfig(num_clients=nclients, num_clusters=4,
                    select_ratio=0.25, local_epochs=1,
                    scheme="gradient_cluster_auction",
                    sample_window=20, cluster_resamples=2,
                    init_energy_mode="normal", eval_every=10 ** 6,
                    runtime="device", seed=0)
    train, test = make_image_dataset("mnist", n_train=nclients * 125,
                                     n_test=256, seed=0)
    adapter = cnn_adapter("mnist")
    out = {"clients": nclients, "rounds": rounds,
           "warm_rounds": warm_rounds, "timed_rounds": timed_rounds,
           "cells": {}}
    for nu in nus:
        for scheme in zoo:
            cfg = base.replace(non_iid_level=nu, scheme_select=scheme)
            clients = partition_clients(train.y, cfg, seed=0)
            srv = FederatedServer(cfg, adapter, train.x, train.y, clients,
                                  {"x": test.x[:256], "y": test.y[:256]})
            srv.run(rounds=warm_rounds)
            jax.block_until_ready(srv.params)
            t0 = time.time()
            for t in range(warm_rounds, warm_rounds + timed_rounds):
                srv._dispatch_round(t, eval_now=False)
            srv._flush_pending()
            jax.block_until_ready(srv.params)
            wall = time.time() - t0
            for t in range(warm_rounds + timed_rounds, rounds):
                srv._dispatch_round(t, eval_now=False)
            srv._flush_pending()
            acc, _ = jax.device_get(
                srv._eval_step(srv.params, srv._test_dev))
            hist = np.asarray(jax.device_get(srv.state.history))
            row = {
                "rounds_per_s": timed_rounds / wall,
                "test_acc": float(acc),
                "energy_std": float(srv.logs[-1].energy_std),
                "fairness_hist_std": float(np.std(hist)),
            }
            if scheme == "longterm_auction":
                ss = srv.state.scheme_state
                row["budget_spent_total"] = float(
                    jax.device_get(ss.spent))
                row["budget_queue_final"] = float(
                    jax.device_get(ss.queue))
            out["cells"][f"{scheme}_nu{nu}"] = row
            _row(f"scheme_zoo_{scheme}_nu{nu}",
                 wall / timed_rounds * 1e6,
                 f"rounds_per_s={row['rounds_per_s']:.2f} "
                 f"acc={row['test_acc']:.3f} "
                 f"energy_std={row['energy_std']:.3f} "
                 f"fairness={row['fairness_hist_std']:.2f}")
    _save("scheme_zoo", out)
    c = out["cells"]
    _summary("scheme_zoo", clients=nclients, rounds=rounds,
             warm_rounds_per_s_paper=c["paper_nu1.0"]["rounds_per_s"],
             **{f"acc_{s}": c[f"{s}_nu1.0"]["test_acc"] for s in zoo},
             **{f"energy_std_{s}": c[f"{s}_nu1.0"]["energy_std"]
                for s in zoo},
             **{f"fairness_{s}": c[f"{s}_nu1.0"]["fairness_hist_std"]
                for s in zoo},
             budget_spent=c["longterm_auction_nu1.0"]
             ["budget_spent_total"])


def bench_robust_agg(quick: bool):
    """Byzantine robustness + defended-aggregation overhead: final test
    accuracy and warm FL rounds/sec across adversary fraction 0 / 0.1 /
    0.3 x defense off (plain FedAvg) / on (screened trimmed-mean), scale
    attack, device runtime.  The (0, off) cell is the attack-free
    bit-exact baseline; (0, on) prices the screened path on a clean
    fleet (~4% warm rounds/sec: per-client delta materialization + the
    sort-based screen); the 0.3 column is the headline: undefended
    FedAvg degrades while the screened aggregation recovers to within
    ~2 points of the attack-free accuracy."""
    from repro.configs.base import FLConfig
    from repro.core.adapters import cnn_adapter
    from repro.core.server import FederatedServer
    from repro.data.partition import partition_clients
    from repro.data.synthetic import make_image_dataset

    nclients = 24 if quick else 32
    # a wide timed window amortizes host timing jitter: the overhead
    # headline compares two separately-timed runs, so per-window noise
    # must be well under the <2% claim it prices
    warm_rounds, timed_rounds = (2, 4) if quick else (5, 20)
    # full mode runs to convergence: the clean baseline reaches ~0.99 by
    # round 60 under this lr/nu, so the 0.3-adversary column separates
    # (undefended collapses to chance, screened recovers within ~2 pts)
    rounds = 6 if quick else 60
    base = FLConfig(num_clients=nclients, num_clusters=4,
                    select_ratio=0.3, local_epochs=2, lr=0.1,
                    non_iid_level=0.3,
                    scheme="gradient_cluster_auction",
                    sample_window=20, cluster_resamples=2,
                    init_energy_mode="normal", eval_every=10 ** 6,
                    runtime="device", attack="scale", seed=0)
    train, test = make_image_dataset("mnist", n_train=nclients * 150,
                                     n_test=256, seed=0)
    adapter = cnn_adapter("mnist")
    out = {"clients": nclients, "rounds": rounds,
           "warm_rounds": warm_rounds, "timed_rounds": timed_rounds,
           "attack": "scale", "cells": {}}
    for frac in (0.0, 0.1, 0.3):
        for defense in ("none", "trimmed"):
            cfg = base.replace(adversary_frac=frac, defense=defense)
            clients = partition_clients(train.y, cfg, seed=0)
            srv = FederatedServer(cfg, adapter, train.x, train.y, clients,
                                  {"x": test.x[:256], "y": test.y[:256]})
            srv.run(rounds=warm_rounds)
            jax.block_until_ready(srv.params)
            t0 = time.time()
            for t in range(warm_rounds, warm_rounds + timed_rounds):
                srv._dispatch_round(t, eval_now=False)
            srv._flush_pending()
            jax.block_until_ready(srv.params)
            wall = time.time() - t0
            for t in range(warm_rounds + timed_rounds, rounds):
                srv._dispatch_round(t, eval_now=False)
            srv._flush_pending()
            acc, _ = jax.device_get(
                srv._eval_step(srv.params, srv._test_dev))
            row = {"rounds_per_s": timed_rounds / wall,
                   "test_acc": float(acc)}
            if srv.defended:
                row.update(srv.defense_totals)
            out["cells"][f"frac{frac}_{defense}"] = row
            _row(f"robust_agg_f{frac}_{defense}",
                 wall / timed_rounds * 1e6,
                 f"rounds_per_s={row['rounds_per_s']:.2f} "
                 f"acc={row['test_acc']:.3f}")
    cells = out["cells"]
    clean = cells["frac0.0_none"]
    out["overhead_defended"] = (clean["rounds_per_s"]
                                / cells["frac0.0_trimmed"]["rounds_per_s"]
                                - 1.0)
    out["attack_drop_0.3"] = (clean["test_acc"]
                              - cells["frac0.3_none"]["test_acc"])
    out["defended_gap_0.3"] = (clean["test_acc"]
                               - cells["frac0.3_trimmed"]["test_acc"])
    _row("robust_agg_summary", 0.0,
         f"overhead={out['overhead_defended'] * 100:.1f}% "
         f"attack_drop={out['attack_drop_0.3']:.3f} "
         f"defended_gap={out['defended_gap_0.3']:.3f}")
    _save("robust_agg", out)
    _summary("robust_agg", clients=nclients, rounds=rounds,
             acc_clean=clean["test_acc"],
             acc_attacked_undefended=cells["frac0.3_none"]["test_acc"],
             acc_attacked_defended=cells["frac0.3_trimmed"]["test_acc"],
             acc_f01_undefended=cells["frac0.1_none"]["test_acc"],
             acc_f01_defended=cells["frac0.1_trimmed"]["test_acc"],
             warm_rounds_per_s_clean=clean["rounds_per_s"],
             warm_rounds_per_s_defended=cells["frac0.0_trimmed"]
             ["rounds_per_s"],
             overhead_defended=out["overhead_defended"],
             attack_drop=out["attack_drop_0.3"],
             defended_gap=out["defended_gap_0.3"])


# ----------------------------------------------------------------------
# macro: self-healing server (ISSUE 10 acceptance run)
# ----------------------------------------------------------------------

def bench_self_healing(quick: bool):
    """The self-healing acceptance comparison: a sub_clip adversary
    coalition (30% of the fleet, colluding just under the static clip
    threshold) against (a) no defense at all, (b) the static clip — it
    never touches a sub-threshold row, so accuracy measurably degrades —
    and (c) the full self-healing stack: adaptive MAD-band screening +
    reputation-priced bidding + the divergence watchdog.  The headline
    is ``selfheal_gap`` (within 0.05 of the clean baseline in the full
    60-round run) vs ``static_gap``; ``watchdog_overhead`` prices the
    watchdog's warm-loop hooks (delta scaling + snapshot refs) on a
    clean run."""
    from repro.configs.base import FLConfig
    from repro.core.adapters import cnn_adapter
    from repro.core.server import FederatedServer
    from repro.data.partition import partition_clients
    from repro.data.synthetic import make_image_dataset

    nclients = 24 if quick else 32
    warm_rounds, timed_rounds = (2, 4) if quick else (5, 20)
    rounds = 6 if quick else 60
    eval_every = 3 if quick else 10
    base = FLConfig(num_clients=nclients, num_clusters=4,
                    select_ratio=0.3, local_epochs=2, lr=0.1,
                    non_iid_level=0.3,
                    scheme="gradient_cluster_auction",
                    sample_window=20, cluster_resamples=2,
                    init_energy_mode="normal", eval_every=eval_every,
                    runtime="device", seed=0)
    train, test = make_image_dataset("mnist", n_train=nclients * 150,
                                     n_test=256, seed=0)
    adapter = cnn_adapter("mnist")
    clients = partition_clients(train.y, base, seed=0)

    def cell(label, **kw):
        cfg = base.replace(**kw)
        srv = FederatedServer(cfg, adapter, train.x, train.y, clients,
                              {"x": test.x[:256], "y": test.y[:256]})
        srv.run(rounds=warm_rounds)
        jax.block_until_ready(srv.params)
        t0 = time.time()
        for t in range(warm_rounds, warm_rounds + timed_rounds):
            srv._dispatch_round(t, eval_now=False)
        srv._flush_pending()
        jax.block_until_ready(srv.params)
        wall = time.time() - t0
        for t in range(warm_rounds + timed_rounds, rounds):
            due = t % eval_every == 0 or t == rounds - 1
            srv._dispatch_round(t, eval_now=due)
            if due and cfg.watchdog_enabled:
                srv._flush_pending()       # watchdog detection boundary
        srv._flush_pending()
        acc, _ = jax.device_get(srv._eval_step(srv.params, srv._test_dev))
        row = {"rounds_per_s": timed_rounds / wall, "test_acc": float(acc)}
        if srv.defended:
            row.update(srv.defense_totals)
        if cfg.watchdog_enabled:
            row.update(srv.watchdog_totals)
        _row(f"self_healing_{label}", wall / timed_rounds * 1e6,
             f"rounds_per_s={row['rounds_per_s']:.2f} "
             f"acc={row['test_acc']:.3f}")
        return row

    atk = dict(attack="sub_clip", adversary_frac=0.3)
    out = {"clients": nclients, "rounds": rounds, "attack": "sub_clip",
           "adversary_frac": 0.3, "cells": {}}
    out["cells"]["clean"] = cell("clean")
    out["cells"]["clean_watchdog"] = cell("clean_watchdog", watchdog="on")
    out["cells"]["undefended"] = cell("undefended", **atk)
    out["cells"]["static_clip"] = cell("static_clip", defense="clip",
                                       **atk)
    out["cells"]["selfheal"] = cell(
        "selfheal", defense="clip", defense_mode="adaptive",
        reputation_mode="price", watchdog="on", **atk)

    cells = out["cells"]
    clean = cells["clean"]
    out["static_gap"] = clean["test_acc"] - cells["static_clip"]["test_acc"]
    out["selfheal_gap"] = clean["test_acc"] - cells["selfheal"]["test_acc"]
    out["watchdog_overhead"] = (
        clean["rounds_per_s"]
        / cells["clean_watchdog"]["rounds_per_s"] - 1.0)
    _row("self_healing_summary", 0.0,
         f"static_gap={out['static_gap']:.3f} "
         f"selfheal_gap={out['selfheal_gap']:.3f} "
         f"wd_overhead={out['watchdog_overhead'] * 100:.1f}%")
    _save("self_healing", out)
    _summary("self_healing", clients=nclients, rounds=rounds,
             acc_clean=clean["test_acc"],
             acc_attacked_undefended=cells["undefended"]["test_acc"],
             acc_attacked_static_clip=cells["static_clip"]["test_acc"],
             acc_attacked_selfheal=cells["selfheal"]["test_acc"],
             static_gap=out["static_gap"],
             selfheal_gap=out["selfheal_gap"],
             selfheal_within_005=bool(out["selfheal_gap"] <= 0.05),
             rollbacks_selfheal=cells["selfheal"].get("rollbacks", 0),
             screened_selfheal=cells["selfheal"].get("screened", 0),
             warm_rounds_per_s_clean=clean["rounds_per_s"],
             warm_rounds_per_s_selfheal=cells["selfheal"]["rounds_per_s"],
             watchdog_overhead=out["watchdog_overhead"])


# ----------------------------------------------------------------------
# paper figures (FL simulations)
# ----------------------------------------------------------------------

def _fl_run(scheme, nu, aggregator, rounds, quick, seed=0, dataset="mnist"):
    from repro.configs.base import FLConfig
    from repro.core.adapters import cnn_adapter
    from repro.core.server import FederatedServer
    from repro.data.partition import partition_clients
    from repro.data.synthetic import make_image_dataset
    nclients = 30 if quick else 100
    pool = 3000 if quick else 12_000
    cfg = FLConfig(num_clients=nclients, num_clusters=5 if quick else 10,
                   select_ratio=0.1, rounds=rounds, lr=0.05,
                   non_iid_level=nu, scheme=scheme, aggregator=aggregator,
                   init_energy_mode="normal",
                   sample_window=30 if quick else 50,
                   cluster_resamples=3 if quick else 5, seed=seed)
    train, test = make_image_dataset(dataset, n_train=pool,
                                     n_test=pool // 6, seed=seed)
    clients = partition_clients(train.y, cfg, seed=seed)
    srv = FederatedServer(cfg, cnn_adapter(dataset), train.x, train.y,
                          clients, {"x": test.x[:500], "y": test.y[:500]})
    logs = srv.run()
    return {
        "acc": [l.test_acc for l in logs],
        "loss": [l.test_loss for l in logs],
        "energy_std": [l.energy_std for l in logs],
        "mean_bid": [l.mean_bid for l in logs],
        "server_reward": [l.server_reward for l in logs],
        "client_reward_sum": [l.client_reward_sum for l in logs],
        "vds_gap": [l.vds_gap for l in logs],
    }


SCHEMES = {
    "Gradient-Cluster-Auction": "gradient_cluster_auction",
    "Gradient-Cluster-Random": "gradient_cluster_random",
    "Weights-Cluster-Random": "weights_cluster_random",
    "Random": "random",
}


def bench_fig4(quick: bool):
    """Fig 4: accuracy/loss vs rounds — gradient vs weights clustering vs
    random FedAvg (nu=1, imbalanced)."""
    rounds = 8 if quick else 30
    out = {}
    for label in ("Gradient-Cluster-Random", "Weights-Cluster-Random",
                  "Random"):
        t0 = time.time()
        r = _fl_run(SCHEMES[label], 1.0, "fedavg", rounds, quick)
        out[label] = r
        _row(f"fig4_{label}", (time.time() - t0) * 1e6 / rounds,
             f"final_acc={r['acc'][-1]:.3f} final_loss={r['loss'][-1]:.3f}")
    _save("fig4_convergence", out)


def bench_fig5(quick: bool):
    """Fig 5: price (mean winning bid) and reward vs rounds (reward model 2,
    eq 16)."""
    rounds = 8 if quick else 30
    t0 = time.time()
    r = _fl_run("gradient_cluster_auction", 1.0, "fedavg", rounds, quick)
    _row("fig5_price_reward", (time.time() - t0) * 1e6 / rounds,
         f"bid_first={r['mean_bid'][0]:.3f} bid_last={r['mean_bid'][-1]:.3f}"
         f" server_reward_last={r['server_reward'][-1]:.3f}")
    _save("fig5_price_reward", r)


def bench_fig6_7_8(quick: bool, aggregator: str = "fedavg"):
    """Fig 6 (Avg) / 7 (Prox) / 8 (nu=0.5): accuracy vs rounds for the
    schemes at nu in {1, 0.8, 0.5}."""
    rounds = 8 if quick else 30
    nus = [1.0] if quick else [1.0, 0.8, 0.5]
    out = {}
    for nu in nus:
        for label, scheme in SCHEMES.items():
            if label == "Weights-Cluster-Random":
                continue   # fig6-8 compare the other three
            t0 = time.time()
            r = _fl_run(scheme, nu, aggregator, rounds, quick)
            out[f"{label}_nu{nu}"] = r
            _row(f"fig6_{aggregator}_nu{nu}_{label}",
                 (time.time() - t0) * 1e6 / rounds,
                 f"final_acc={r['acc'][-1]:.3f}")
    _save(f"fig6_8_accuracy_{aggregator}", out)


def bench_fig9_10(quick: bool):
    """Fig 9/10: energy-balance std vs rounds, all schemes. Needs enough
    rounds for selection pressure to differentiate the schemes (the paper
    runs 100+)."""
    rounds = 8 if quick else 60
    out = {}
    for label, scheme in SCHEMES.items():
        t0 = time.time()
        r = _fl_run(scheme, 1.0, "fedavg", rounds, quick)
        out[label] = r["energy_std"]
        _row(f"fig9_energy_{label}", (time.time() - t0) * 1e6 / rounds,
             f"final_energy_std={r['energy_std'][-1]:.3f}")
    _save("fig9_energy_balance", out)


def bench_virtual_dataset(quick: bool):
    """Fig 3 concept: TV distance of the round virtual dataset from the
    global distribution, cluster selection vs random."""
    rounds = 10 if quick else 30
    gaps = {}
    for label in ("Gradient-Cluster-Random", "Random"):
        r = _fl_run(SCHEMES[label], 1.0, "fedavg", rounds, quick)
        gaps[label] = float(np.mean(r["vds_gap"]))
    _row("fig3_vds_gap", 0.0,
         f"cluster={gaps['Gradient-Cluster-Random']:.3f} "
         f"random={gaps['Random']:.3f}")
    _save("fig3_vds_gap", gaps)


BENCHES = {
    "kernels": bench_kernels,
    "clustering": bench_clustering,
    "selection": bench_selection,
    "fleet_dynamics": bench_fleet_dynamics,
    "robust_agg": bench_robust_agg,
    "self_healing": bench_self_healing,
    "scheme_zoo": bench_scheme_zoo,
    "fig3": bench_virtual_dataset,
    "fig4": bench_fig4,
    "fig5": bench_fig5,
    "fig6": lambda q: bench_fig6_7_8(q, "fedavg"),
    "fig7": lambda q: bench_fig6_7_8(q, "fedprox"),
    "fig9": bench_fig9_10,
}


def main() -> None:
    from repro import obs
    from repro.launch.compile_cache import use_compile_cache
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help=f"comma list of {list(BENCHES)}")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the selected "
                         "benchmarks for TensorBoard/Perfetto")
    args = ap.parse_args()
    use_compile_cache()
    names = args.only.split(",") if args.only else list(BENCHES)
    print("name,us_per_call,derived")
    with obs.maybe_profile(args.profile_dir):
        for n in names:
            BENCHES[n](args.quick)


if __name__ == "__main__":
    main()
