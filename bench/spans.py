"""Split the device's idle gaps in a traced window by the program's own
spans.

While the program records (a sink attached, as in a traced run of
``fl_rounds``), each ``repro.obs`` span is also a ``jax.profiler``
annotation of the span's name, on the host plane of the trace.
:func:`program_gaps` takes the idle gaps of device 0 inside the
:data:`bench.trace.WINDOW` annotation exactly as
:func:`bench.trace.reduce_profile` does, keeps the label that reduction
gives each (the innermost ``bench/...`` annotation over its midpoint, or
``host``), and splits each gap's time by the innermost program span the
host was in, at any depth; time in no program span is ``host``.
Program spans are the host events whose name starts with one of the
program's span layers (:data:`LAYERS`); JAX's own host events never do.
They nest, as the program's spans are opened on one thread.

The readers of ``bench/metrics/`` do not use this yet: ``fl_rounds``
would have to hand them the traced window's gaps (PERF.md, Open
questions).
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench import trace as TR

LAYERS = ("round/", "cohort/", "fleet/", "run/", "cluster/", "selection/")

Gap = Tuple[str, str, float]       # (bench label, program label, seconds)


def _segments(spans) -> List[Tuple[int, int, str]]:
    """Disjoint ``(start, end, innermost span)`` pieces, in order, of
    nested ``(start, end, name)`` spans."""
    out, stack, t = [], [], 0

    def close(until):
        nonlocal t
        while stack and stack[-1][0] <= until:
            end, name = stack.pop()
            out.append((t, end, name))
            t = end

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        close(a)
        if stack:
            out.append((t, a, stack[-1][1]))
        stack.append((b, name))
        t = a
    close(float("inf"))
    return [s for s in out if s[1] > s[0]]


def _split(segs, ends, a, b):
    """``[(program label, ns)]`` of the interval ``[a, b]``."""
    out, covered = [], 0
    i = bisect.bisect_right(ends, a)
    while i < len(segs) and segs[i][0] < b:
        s0, s1, name = segs[i]
        ns = min(s1, b) - max(s0, a)
        out.append((name, ns))
        covered += ns
        i += 1
    if b - a > covered:
        out.append(("host", b - a - covered))
    return out


def program_gaps(data) -> List[Gap]:
    """``data`` is a ``jax.profiler.ProfileData``; one or more entries
    per idle gap, whose seconds add up to the gap's."""
    window, bench, program, ops = [], [], [], []
    for plane in data.planes:
        m = TR.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m is not None:
                if int(m.group(1)) == 0 and line.name == TR.OPS_LINE:
                    ops += [(int(e.start_ns), int(e.end_ns), "")
                            for e in line.events]
                continue
            for e in line.events:
                ev = (int(e.start_ns), int(e.end_ns), e.name)
                if e.name == TR.WINDOW:
                    window.append(ev)
                elif e.name.startswith(TR.PREFIX):
                    bench.append(ev)
                elif e.name.startswith(LAYERS):
                    program.append(ev)
    if not window:
        raise ValueError(f"no {TR.WINDOW!r} host annotation in the trace")
    if not ops:
        raise ValueError("no 'XLA Ops' line of device 0 in the trace")
    lo, hi = window[0][:2]
    busy = TR._union([(a, b) for a, b, _ in TR._clip(ops, lo, hi)])
    bench.sort()
    starts = [a for a, _, _ in bench]
    segs = _segments(program)
    ends = [s1 for _, s1, _ in segs]
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    out = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            label = TR._label(bench, starts, (a + b) / 2)
            out += [(label, name, ns * 1e-9)
                    for name, ns in _split(segs, ends, a, b)]
    return out


def split(gaps: List[Gap], bench_label: Optional[str] = None
          ) -> Dict[str, float]:
    """Idle seconds by program label, over the gaps that the bench
    reduction labels ``bench_label`` (all gaps where it is None)."""
    out: Dict[str, float] = defaultdict(float)
    for b, p, s in gaps:
        if bench_label is None or b == bench_label:
            out[p] += s
    return dict(out)
