"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time,
per-program device time and idle gaps labelled by the harness's host
annotations.

Devices are the planes named ``/device:TPU:<n>``.  Busy time is the
union of the intervals of the events on each device's ``XLA Ops`` line,
clipped to the window and averaged over the devices.  Per-program time
sums the events of the ``XLA Modules`` line by program name (the
``jit_<function>`` name without XLA's ``(<id>)`` suffix), and per-op time
those of the ``XLA Ops`` line by HLO instruction name (a loop's ``while``
spans the ops of its body).  The window is
the host annotation named :data:`WINDOW` (the harness wraps the traced
rounds in it); an idle gap of device 0 inside it is labelled with the
innermost other ``bench/...`` host annotation that covers its midpoint,
or ``host`` where none does.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

WINDOW = "bench/traced"
PREFIX = "bench/"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclass
class Summary:
    window_s: float
    busy_s: float                                 # averaged over devices
    devices: int
    module_s: Dict[str, float]                    # per program, all devices
    op_s: Dict[str, float]                        # per op name, all devices
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    def module_time(self, pattern: str) -> float:
        """Device seconds of every program whose name matches
        ``pattern`` (a regular expression, matched from the start)."""
        rx = re.compile(pattern)
        return sum(s for m, s in self.module_s.items() if rx.match(m))

    def idle_by_label(self) -> Dict[str, float]:
        out: Dict[str, float] = defaultdict(float)
        for label, s in self.gaps:
            out[label] += s
        return dict(out)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_by_label().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle[:top]]}


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _clip(events, lo, hi):
    """``(start, end, name)`` events cut to the window ``[lo, hi]``."""
    return [(max(a, lo), min(b, hi), n) for a, b, n in events
            if b > lo and a < hi]


def _label(inner, starts, t, depth: int = 4) -> str:
    """The latest-starting of the last ``depth`` annotations before ``t``
    that covers ``t`` (the innermost, where they nest), else ``host``."""
    i = bisect.bisect_right(starts, t)
    for a, b, name in reversed(inner[max(i - depth, 0):i]):
        if b >= t:
            return name
    return "host"


def reduce_profile(data) -> Summary:
    """``data`` is a ``jax.profiler.ProfileData``."""
    annotations: List[Tuple[int, int, str]] = []
    ops: Dict[int, List[Tuple[int, int, str]]] = defaultdict(list)
    modules: Dict[int, List[Tuple[int, int, str]]] = defaultdict(list)
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m is not None and line.name in (OPS_LINE, MODULES_LINE):
                dst = ops if line.name == OPS_LINE else modules
                dst[int(m.group(1))] += [
                    (int(e.start_ns), int(e.end_ns), e.name)
                    for e in line.events]
            elif m is None:
                annotations += [(int(e.start_ns), int(e.end_ns), e.name)
                                for e in line.events
                                if e.name.startswith(PREFIX)]
    windows = [(a, b) for a, b, n in annotations if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} host annotation in the trace")
    if not ops:
        raise ValueError("no device plane with an 'XLA Ops' line in the "
                         "trace")
    lo, hi = windows[0]
    busy = {}
    for dev, evs in ops.items():
        busy[dev] = _union([(a, b) for a, b, _ in _clip(evs, lo, hi)])
    busy_ns = sum(sum(b - a for a, b in iv) for iv in busy.values())

    op_s: Dict[str, float] = defaultdict(float)
    for evs in ops.values():
        for a, b, name in _clip(evs, lo, hi):
            op_s[_op_name(name)] += (b - a) * 1e-9
    module_s: Dict[str, float] = defaultdict(float)
    for evs in modules.values():
        for a, b, name in _clip(evs, lo, hi):
            module_s[_SUFFIX.sub("", name)] += (b - a) * 1e-9

    inner = sorted((a, b, n) for a, b, n in annotations if n != WINDOW)
    starts = [a for a, _, _ in inner]
    gaps = []
    dev0 = busy[min(busy)]
    edges = [lo] + [x for iv in dev0 for x in iv] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((_label(inner, starts, (a + b) / 2), (b - a) * 1e-9))
    return Summary(window_s=(hi - lo) * 1e-9,
                   busy_s=busy_ns * 1e-9 / len(busy), devices=len(busy),
                   module_s=dict(module_s), op_s=dict(op_s), gaps=gaps)


def reduce_file(path) -> Summary:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)))
