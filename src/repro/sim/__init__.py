"""Cohort execution engine: batched local training for whole auction
cohorts (see DESIGN.md §Cohort runtimes / §Round pipeline).

  * cohort.py  — ``HostPlanCache`` memoizes the per-client plan
    structure + local data gathers; ``pack_feature_pass`` packs every
    client's shard into padded, size-bucketed minibatch tensors with
    per-step validity masks for the stage-1 weight feature pass.
  * fleet.py   — ``FleetStore``: the whole fleet packed once into
    device-resident capacity-class tensors; per-round cohorts assemble as
    tiny int index plans (the ``device`` runtime's data plane).
  * engine.py  — runs local SGD/FedProx epochs for a whole capacity
    class as one compiled program: ``jax.vmap`` over clients,
    ``jax.lax.scan`` over minibatch steps, fused weighted aggregation;
    plus the stage-1 feature passes.
  * runtime.py — the ``CohortRuntime`` protocol and its two backends
    (``sequential`` reference oracle, ``device`` resident-fleet
    pipeline).
  * dynamics.py — the jittable per-round fault model (availability
    churn, stragglers, FedCS-style deadline misses) the fused round
    control plane composes in when ``cfg.dynamics_enabled``.
"""
from repro.sim.cohort import CohortBucket, HostPlanCache, pack_feature_pass
from repro.sim.dynamics import (DynamicsState, dynamics_key, fault_step,
                                init_dynamics, split_outcomes)
from repro.sim.engine import CohortEngine
from repro.sim.fleet import CapacityClass, ClassBatch, FleetStore
from repro.sim.runtime import (CohortRuntime, DeviceRuntime,
                               SequentialRuntime, make_runtime)

__all__ = [
    "CohortBucket", "HostPlanCache", "pack_feature_pass",
    "CohortEngine",
    "CapacityClass", "ClassBatch", "FleetStore",
    "CohortRuntime", "DeviceRuntime", "SequentialRuntime", "make_runtime",
    "DynamicsState", "dynamics_key", "fault_step", "init_dynamics",
    "split_outcomes",
]
