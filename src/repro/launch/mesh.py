"""Production mesh builders.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init; smoke tests and benches must keep seeing 1 device.

Mesh semantics (DESIGN.md §5):
  * ``data``  — FSDP + batch parallelism (16-way per pod)
  * ``model`` — tensor/expert parallelism (16-way)
  * ``pod``   — federated cohorts: parameters replicated across pods, one
    cross-pod all-reduce per FL aggregation round.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh():
    """1x1 mesh with the production axis names — lets every pjit'd function
    run unchanged on the single CPU device for tests/examples."""
    return _make_mesh((1, 1), ("data", "model"))


def make_cohort_mesh(num_devices: int = 0):
    """Mesh for the device cohort runtime (repro.sim ``--runtime device``):
    every class call's client axis is shard_map'd over ``data``, params
    and the class store stay replicated, and the weighted FedAvg partial
    is psum-reduced on-mesh.

    ``num_devices`` caps the data axis (0 = all local devices). With one
    device this degrades to the 1-device debug mesh (the device runtime
    then skips the mesh unless it is handed one, as tests do); CI
    additionally forces an 8-device CPU mesh via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — that flag must
    be set before first jax init (same caveat as the dry-run's 512).
    """
    n_avail = jax.local_device_count()
    n = min(num_devices, n_avail) if num_devices > 0 else n_avail
    if n <= 1:
        return make_debug_mesh()
    return _make_mesh((n, 1), ("data", "model"))


# TPU v5e hardware constants for the roofline model (per chip).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link (~ per-chip collective bw)
HBM_PER_CHIP = 16 * 2**30       # 16 GiB
