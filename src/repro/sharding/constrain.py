"""Sharding-constraint helper usable from model code.

``maybe_constrain(x, axis0, axis1, ...)`` applies
``with_sharding_constraint`` when an ambient abstract mesh (set via
``jax.sharding.set_mesh``) carries the named axes; otherwise it is a no-op,
so the same model code runs in single-device tests and in the 512-device
dry-run. Axis entries may be None, a name, or a tuple of names.
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import PartitionSpec as P

_STATE = threading.local()


@contextlib.contextmanager
def forbid_axes(*axes):
    """Trace-time context: named axes that model-internal constraints must
    NOT use. The FL-round step vmaps cohorts over 'pod'; inner activation
    constraints mentioning 'pod' would force cross-pod resharding of
    per-cohort tensors."""
    prev = getattr(_STATE, "forbidden", frozenset())
    _STATE.forbidden = prev | set(axes)
    try:
        yield
    finally:
        _STATE.forbidden = prev


def _filter_entry(mesh_axes, entry):
    """Keep only axis names present in the mesh (tuples are filtered
    element-wise, e.g. ('pod','data') -> 'data' on the single-pod mesh)."""
    if entry is None:
        return None
    if isinstance(entry, tuple):
        kept = tuple(e for e in entry if e in mesh_axes)
        if not kept:
            return None
        return kept if len(kept) > 1 else kept[0]
    return entry if entry in mesh_axes else None


def maybe_constrain(x, *axes):
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return x
    names = set(am.axis_names)
    names -= getattr(_STATE, "forbidden", frozenset())
    # inside a shard_map manual region the manual axes (e.g. 'pod' in the
    # FL-round step) must not appear in sharding constraints
    names -= {n for n, t in zip(am.axis_names, am.axis_types)
              if t == jax.sharding.AxisType.Manual}
    spec = P(*[_filter_entry(names, a) for a in axes])
    return jax.lax.with_sharding_constraint(x, spec)
