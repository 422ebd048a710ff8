"""What every driver and reader shares: finding a cell's files by name,
the result of a run, the per-layer readers and the result line.

A cell (``workloads`` entry of ``BENCHMARK.json``) names a configuration
and a traffic mix.  Its files are found by those names:

* ``bench/configs/<config>.json``: the configuration as it is run;
* ``bench/traffic/<traffic>.json``: the traffic mix, naming its driver
  and the limits of the correctness check;
* ``bench/drivers/<driver>.py``: ``run(cell) -> Result``;
* ``bench/metrics/<metric>.py``: ``read(ctx) -> float``, one per
  per-layer metric, raising :class:`NothingToRead` when the trace or
  counters it reads are absent.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Tuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NothingToRead(LookupError):
    """A per-layer reader found nothing of what it reads: the run's line
    leaves that metric out."""


@dataclass
class Cell:
    """One run of one cell, as the driver gets it."""

    name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    t_start: float                # perf_counter at process start
    peak_flops: float             # per chip, for the utilization metrics
    chips: int = 1
    out_dir: Path = field(default_factory=lambda: ROOT / ".bench_out")


@dataclass
class Result:
    """What a driver hands back.

    ``e2e``: end-to-end metric values by name; ``ctx``: what the
    per-layer readers read (``trace`` holds the reduced profile of a
    traced run); ``checks``: each compared number with its limit."""

    e2e: Dict[str, float]
    ctx: Dict[str, Any]
    checks: Dict[str, Tuple[float, float]]
    attempted: int
    failed: int
    memory_peak_bytes: int

    @property
    def correct(self) -> bool:
        return (bool(self.checks) and self.failed == 0
                and all(v <= lim for v, lim in self.checks.values()))


def use_compile_cache() -> None:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``.jax_cache`` at the root of the checkout (a fixed
    path, so that a later run finds it); every program goes in, however
    quick to compile, so that a second run of a cell compiles nothing."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def load_json(path: Path) -> Any:
    return json.loads(Path(path).read_text())


def load_module(path: Path):
    """Import a driver or reader file by its path (its name may hold
    dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def end_to_end_metrics(spec: dict, cell: str) -> List[dict]:
    return [m for m in spec["end_to_end"] if _applies(m, cell)]


def per_layer_metrics(spec: dict, cell: str) -> List[dict]:
    e2e = {m["name"] for m in end_to_end_metrics(spec, cell)}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def read_per_layer(metrics: List[dict], ctx: Dict[str, Any]
                   ) -> Dict[str, dict]:
    """Run each metric's reader; a reader with nothing to read is left
    out of the line and named on stderr."""
    out = {}
    for m in metrics:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        try:
            value = reader.read(ctx)
        except NothingToRead as e:
            print(f"bench: {m['name']}: nothing to read: {e}",
                  file=sys.stderr)
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def execute(spec: dict, cell: Cell, device: dict) -> str:
    """Run ``cell`` through its driver, print each compared number and its
    limit on stderr, and return the result line: ``correct``,
    ``attempted``, ``failed``, ``metrics`` (end-to-end, or per-layer when
    traced), ``device`` (given: platform, kind, count), ``breakdown`` when
    traced, and ``checks`` last."""
    driver = load_module(BENCH / "drivers"
                         / f"{cell.traffic['driver']}.py")
    res = driver.run(cell)
    device = dict(device, memory_peak_bytes=res.memory_peak_bytes)
    line: Dict[str, Any] = {"correct": res.correct,
                            "attempted": res.attempted,
                            "failed": res.failed}
    if cell.trace:
        tr = res.ctx["trace"]
        line["metrics"] = read_per_layer(per_layer_metrics(spec, cell.name),
                                         res.ctx)
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        line["device"] = device
        line["breakdown"] = tr.breakdown()
    else:
        line["metrics"] = {m["name"]: {"value": res.e2e[m["name"]],
                                       "unit": m["unit"]}
                           for m in end_to_end_metrics(spec, cell.name)}
        line["device"] = device
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in res.checks.items()}
    for name, (value, limit) in res.checks.items():
        print(f"check {name}: {value!r} (limit {limit!r})", file=sys.stderr)
    return json.dumps(line)
