"""Lightweight span tracing for the round pipeline.

``span("round/dispatch", round=t)`` is a context manager that records a
``{"kind": "span", name, id, parent, depth, t0, dur_s, **meta}`` event on
exit.  Spans nest through a thread-local stack (each thread traces its
own tree), use the monotonic clock (registry epoch), and are safe to
leave in hot paths: while nothing records (``OBS.recording`` is False)
``span()`` returns a shared null context manager (one branch + one
attribute load per call); while recording the cost is two
``perf_counter`` reads, one ``jax.profiler.TraceAnnotation`` and one
buffered dict append at exit — no I/O, no device sync.

The annotation carries exactly the span's name, so under a profiler
capture the span's host interval lies on the device trace's clock and a
device idle gap can be attributed to the innermost span the host was in.

The async server records *dispatch* spans (``round/dispatch`` and its
children) separately from *drain* spans (``round/drain``): a dispatch
span measures only the host time to enqueue the round's work, so the
pipeline's device/host overlap shows up as dispatch spans much shorter
than the wall time between drains instead of being averaged away.
"""
from __future__ import annotations

import itertools
import threading

from jax.profiler import TraceAnnotation

from repro.obs.registry import OBS, now

_ids = itertools.count(1)
_tls = threading.local()


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class _Span:
    __slots__ = ("name", "meta", "t0", "sid", "parent", "ann")

    def __init__(self, name, meta):
        self.name = name
        self.meta = meta

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self.parent = stack[-1].sid if stack else None
        self.sid = next(_ids)
        stack.append(self)
        self.ann = TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = now()
        return self

    def __exit__(self, *exc):
        t1 = now()
        self.ann.__exit__(*exc)
        stack = _tls.stack
        depth = len(stack) - 1
        if stack and stack[-1] is self:
            stack.pop()
        OBS.event("span", name=self.name, id=self.sid, parent=self.parent,
                  depth=depth, t0=round(self.t0, 6),
                  dur_s=round(t1 - self.t0, 6), **self.meta)
        return False


_RESERVED = frozenset(("kind", "ts", "name", "id", "parent", "depth",
                       "t0", "dur_s"))


def span(name: str, **meta):
    """Open a span; a no-op shared context manager while nothing
    records.  ``meta`` must be JSON-serializable host scalars; keys
    clashing with the span schema fields are prefixed ``meta_``."""
    if not OBS.recording:
        return _NULL
    if _RESERVED & meta.keys():
        meta = {(f"meta_{k}" if k in _RESERVED else k): v
                for k, v in meta.items()}
    return _Span(name, meta)
