"""JAX-awareness layer: centralized retrace/compile counters, device
transfer accounting, opt-in profiler capture, and the transfer-guard
sync auditor.

``jax_stats`` is one process-wide tally: traced bodies call
``jax_stats.note_trace(what)`` (a Python side effect, so it fires at
trace/compile time ONLY — counting adds literally nothing to the warm
path), the cohort engine's shape-cache bookkeeping calls ``note_shape``,
and the :func:`device_put` / :func:`device_get` wrappers count explicit
host transfers by direction, bytes and calls.  Tests and benchmarks
snapshot the counters around a warm window to assert "zero retraces" and
"no hidden transfers" (tests/test_obs.py, tests/test_fleet.py).

Two more families:

* **compile time** — ``jax.monitoring`` listeners registered at import
  sum JAX's own compile events: ``compile/trace_s`` (jaxpr tracing),
  ``compile/lower_s`` (jaxpr to MLIR), ``compile/backend_s`` (XLA
  compile, or the load from the persistent cache, which it contains) and
  ``compile/cache_load_s`` (that load alone).  ``compile_s`` is the time
  covered by the first three: a jit traced inside another's trace counts
  once.  They fire only when JAX compiles or loads a program, so the warm
  loop pays nothing.
* **stage-3 work** — ``note_work`` adds what the device runtime's class
  programs run (``stage3/...``: DeviceRuntime), taken only while
  ``OBS.recording``.

The **sync auditor** (:func:`sync_audit`) wraps a code region in jax's
transfer guards for both host directions set to ``disallow``: any
*implicit* host<->device transfer (a numpy array silently fed to a
jitted program, a ``float()`` on a device scalar) raises, while explicit
``jax.device_put`` / ``jax.device_get`` — the transfers the async
pipeline performs on purpose, all routed through the counted wrappers —
stay legal.  Device-to-device transfers are left unguarded: resharding
committed arrays onto a mesh is exactly what the sharded paths are
supposed to do.  CPU caveat: on the CPU backend "device" buffers live in
host RAM, so the guard audits *API-level* sync discipline (which is what
retrace/dispatch stalls care about), not physical PCIe traffic — see
DESIGN.md §Observability.
"""
from __future__ import annotations

import bisect
import contextlib
import threading
from typing import Any, Dict, List, Tuple

import jax

from repro.obs.registry import OBS


class JaxStats:
    """Process-wide retrace / compile / transfer / work counters
    (thread-safe).  ``flushed`` holds the counters as the last
    :meth:`repro.obs.flush` with a sink attached handed them to the
    sinks."""

    def __init__(self):
        self._lock = threading.Lock()
        self.counters: Dict[str, float] = {}
        self.flushed: Dict[str, float] = {}
        self._compile_spans: List[Tuple[float, float]] = []

    def _inc(self, name: str, by: float = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + by

    def note_trace(self, what: str = "jit") -> None:
        """Call from inside a traced body: runs at (re)trace time only."""
        self._inc("traces")
        self._inc(f"traces/{what}")

    def note_shape(self, hit: bool) -> None:
        self._inc("shape_hits" if hit else "shape_misses")

    def note_transfer(self, direction: str, nbytes: int,
                      calls: int = 1) -> None:
        """``direction`` is 'h2d' or 'd2h' (explicit, counted wrappers)."""
        self._inc(f"{direction}_bytes", nbytes)
        self._inc(f"{direction}_calls", calls)

    def note_work(self, what: str, **counts: int) -> None:
        """Add ``counts`` under ``<what>/<name>``."""
        with self._lock:
            for k, v in counts.items():
                key = f"{what}/{k}"
                self.counters[key] = self.counters.get(key, 0) + v

    def note_compile(self, what: str, t0: float, t1: float) -> None:
        """One compile phase over ``[t0, t1]``: adds to ``what`` and to
        ``compile_s``, which counts the union of the phases' intervals
        (kept sorted and disjoint in ``_compile_spans``)."""
        with self._lock:
            c, spans = self.counters, self._compile_spans
            c[what] = c.get(what, 0.0) + (t1 - t0)
            lo = bisect.bisect_left(spans, (t0,))
            if lo and spans[lo - 1][1] >= t0:
                lo -= 1
            hi = lo
            while hi < len(spans) and spans[hi][0] <= t1:
                hi += 1
            total = c.get("compile_s", 0.0)
            for a, b in spans[lo:hi]:
                total -= b - a
                t0, t1 = min(a, t0), max(b, t1)
            spans[lo:hi] = [(t0, t1)]
            c["compile_s"] = total + (t1 - t0)

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self.counters)

    def delta(self, since: Dict[str, float]) -> Dict[str, float]:
        """Counter movement since a :meth:`snapshot` (only nonzero keys)."""
        snap = self.snapshot()
        keys = set(snap) | set(since)
        return {k: snap.get(k, 0) - since.get(k, 0) for k in keys
                if snap.get(k, 0) != since.get(k, 0)}

    def reset(self) -> None:
        with self._lock:
            self.counters.clear()
            self.flushed = {}
            self._compile_spans.clear()


jax_stats = JaxStats()


def _emit_jax_stats() -> None:
    """Flush hook: one ``jax_stats`` event per flush iff counters moved."""
    snap = jax_stats.snapshot()
    if snap and snap != jax_stats.flushed:
        jax_stats.flushed = snap
        OBS.event("jax_stats", **snap)


OBS.add_flush_hook(_emit_jax_stats)

_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "compile/trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/lower_s",
    "/jax/core/compile/backend_compile_duration": "compile/backend_s",
}
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


def _on_time_span(event: str, t0: float, t1: float, **_) -> None:
    what = _COMPILE_PHASES.get(event)
    if what is not None:
        jax_stats.note_compile(what, t0, t1)


def _on_duration(event: str, secs: float, **_) -> None:
    if event == _CACHE_LOAD:
        jax_stats._inc("compile/cache_load_s", secs)


jax.monitoring.register_event_time_span_listener(_on_time_span)
jax.monitoring.register_event_duration_secs_listener(_on_duration)


def _tree_nbytes(tree: Any) -> int:
    return sum(getattr(x, "nbytes", 0) for x in jax.tree.leaves(tree))


def device_put(tree: Any, *args, **kwargs):
    """Counted explicit host->device transfer (pytree-aware).  Using this
    instead of feeding numpy straight into a jitted call is what makes
    the round loop's intended transfers *explicit* — and therefore legal
    under :func:`sync_audit` — while keeping the byte/count books."""
    jax_stats.note_transfer("h2d", _tree_nbytes(tree))
    return jax.device_put(tree, *args, **kwargs)


def device_get(tree: Any):
    """Counted explicit device->host transfer (pytree-aware).  Bytes are
    tallied from the fetched host buffers, so the count itself never adds
    a device sync."""
    out = jax.device_get(tree)
    jax_stats.note_transfer("d2h", _tree_nbytes(out))
    return out


@contextlib.contextmanager
def sync_audit(mode: str = "disallow"):
    """Assert a region performs no *implicit* host transfers (both
    directions guarded; device-to-device left alone — see module
    docstring).  Wrap warm round dispatches:

        with obs.sync_audit():
            server._dispatch_round(t, eval_now)

    Raises jax's XlaRuntimeError at the offending transfer."""
    with jax.transfer_guard_host_to_device(mode), \
            jax.transfer_guard_device_to_host(mode):
        yield


@contextlib.contextmanager
def maybe_profile(profile_dir):
    """Opt-in ``jax.profiler`` trace capture (``--profile-dir``): a
    no-op when ``profile_dir`` is falsy, otherwise the whole region is
    captured for TensorBoard/Perfetto, with the ``obs`` spans recording
    (as profiler annotations) whether or not a sink is attached."""
    if not profile_dir:
        yield
        return
    with OBS.profiling(), jax.profiler.trace(str(profile_dir)):
        yield
