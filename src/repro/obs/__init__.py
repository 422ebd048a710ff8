"""repro.obs — round-pipeline telemetry.

Three layers (see DESIGN.md §Observability):

  * **registry** — structured metrics (counters / gauges / per-round
    series / events) buffered host-side, flushed to pluggable sinks
    (JSONL, in-memory) only at the system's own logging boundaries;
  * **tracing** — nestable monotonic-clock spans
    (``obs.span("round/dispatch")``) cheap enough for the warm loop,
    recording dispatch vs drain time separately so the async pipeline's
    overlap stays visible; each span is also a ``jax.profiler``
    annotation of the same name, so it lies on the device trace's clock;
  * **jaxmon** — JAX awareness: process-wide retrace, compile-time and
    stage-3 work counters (``obs.jax_stats``), counted explicit
    ``device_put``/``device_get`` transfer accounting, the
    ``jax.transfer_guard``-based sync auditor (``obs.sync_audit``) and
    opt-in ``jax.profiler`` capture (``obs.maybe_profile``).

The invariant everything here is built around: instrumentation must not
perturb the system under test — no blocking fetches in the round loop,
no added retraces, near-zero overhead when disabled (no sink attached,
no capture running).
Enforced by tests/test_obs.py.
"""
from repro.obs.jaxmon import (device_get, device_put, jax_stats,
                              maybe_profile, sync_audit)
from repro.obs.registry import OBS, now
from repro.obs.tracing import span

__all__ = ["OBS", "now", "span", "jax_stats", "device_put", "device_get",
           "sync_audit", "maybe_profile", "configure", "flush", "log"]

# singleton conveniences (module-level functions so call sites read as
# ``obs.log(...)`` / ``obs.flush()``)
configure = OBS.configure
flush = OBS.flush
log = OBS.log
