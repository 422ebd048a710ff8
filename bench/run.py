"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (inputs and weights from ``--seed``, the system's own set-up,
warm-up of every program the cell uses) is timed as ``setup_s``; then the
cell's driver measures for ``--seconds``, checks what the measured path
produced against the plain reference, and the last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number with its limit,
which also end standard error.

Exits 3, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for.  JAX's persistent compilation cache is
``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache`` at the root
of the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness as H
    spec = H.load_json(ROOT / "BENCHMARK.json")
    w = H.workload(spec, args.workload)
    traffic = H.load_json(H.BENCH / "traffic" / f"{w['traffic']}.json")
    config = H.load_json(H.BENCH / "configs" / f"{w['config']}.json")
    peaks = H.load_json(H.BENCH / "peaks.json")

    import jax
    H.use_compile_cache()

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < w["chips"]:
        print(f"bench: cell {w['name']} needs {w['chips']} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 3
    kind = devs[0].device_kind
    if kind not in peaks:
        print(f"bench: no peaks for device kind {kind!r} in "
              "bench/peaks.json", file=sys.stderr)
        return 3

    cell = H.Cell(name=w["name"], config=config, traffic=traffic,
                  seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), t_start=T_START,
                  peak_flops=peaks[kind]["bf16_flops_per_s"],
                  chips=w["chips"])
    device = {"platform": devs[0].platform, "kind": kind,
              "count": w["chips"]}
    print(H.execute(spec, cell, device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
