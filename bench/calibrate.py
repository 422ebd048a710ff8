"""Readings for the limits of a cell's correctness check, at the cell's own
size and in one process: the program's numbers on many seeds, and the
control's on some.  The control is the reference computed in bfloat16 and
put in the program's place: on each checked round it answers from the
same inputs, and its answers are compared with the reference as the
program's are.  On the fault seeds, the fault that leaves half of the
program's winners out of FedAvg is read the same way: the reference
averages the first half of the program's winners and answers in its
place.  Benchmark runs never run it.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 \
        --control-seeds 11,12 --fault-seeds 11,12,13 --seconds 5 \
        --out readings.json
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def half_cohort(ref, fleet, inp, got):
    """``got`` with its weights replaced by the reference's FedAvg over
    the first half of its winners (no eval: ``update_gap`` alone)."""
    ids = np.flatnonzero(got.win)
    keep = np.zeros_like(got.win)
    keep[ids[:max(ids.size // 2, 1)]] = True
    params = ref.fedavg(fleet, inp.params, keep, inp.history, np.float32)
    return dataclasses.replace(got, params=params, eval_loss=None)


def worst(readings):
    out = {}
    for r in readings:
        for k, v in r.items():
            out[k] = max(out.get(k, 0.0), v)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    from bench import harness as H
    from bench.reference import fl as REF
    H.use_compile_cache()
    spec = H.load_json(ROOT / "BENCHMARK.json")
    w = H.workload(spec, args.workload)
    traffic = H.load_json(H.BENCH / "traffic" / f"{w['traffic']}.json")
    config = H.load_json(H.BENCH / "configs" / f"{w['config']}.json")
    driver = H.load_module(H.BENCH / "drivers" / f"{traffic['driver']}.py")
    control = {int(s) for s in args.control_seeds.split(",") if s}
    faults = {int(s) for s in args.fault_seeds.split(",") if s}
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cell = H.Cell(name=w["name"], config=config, traffic=traffic,
                      seed=seed, seconds=args.seconds, trace=False,
                      t_start=t0, peak_flops=0.0, chips=w["chips"])
        res, fleet, cases = driver.measure(cell)
        leaves = []

        def compare(inp, got):
            leaves.append({})
            return REF.compare(fleet, inp, got, leaves[-1])

        row = {"seed": seed, "e2e": res.e2e, "rounds": res.attempted,
               "checked": len(cases), "memory_peak_bytes":
               res.memory_peak_bytes,
               "operands": driver.operand_probe(fleet, cases[0][0].params),
               "program": worst(compare(i, a) for i, a in cases)}
        row["program_leaves"] = worst(leaves)
        if seed in control:
            leaves.clear()
            row["control"] = worst(
                compare(i, REF.answer(fleet, i, a.eval_loss is not None,
                                      REF.BF16))
                for i, a in cases)
            row["control_leaves"] = worst(leaves)
        if seed in faults:
            row["half_cohort"] = max(
                compare(i, half_cohort(REF, fleet, i, a))["update_gap"]
                for i, a in cases)
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {"workload": w["name"], "device": jax.devices()[0].device_kind,
               "program_max": worst(r["program"] for r in rows),
               "control_min": {k: min(r["control"][k] for r in rows
                                      if "control" in r)
                               for k in rows[0]["program"]}
               if control else {},
               "half_cohort_min": min((r["half_cohort"] for r in rows
                                       if "half_cohort" in r),
                                      default=None)}
    print(json.dumps(summary), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"rows": rows,
                                              "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
