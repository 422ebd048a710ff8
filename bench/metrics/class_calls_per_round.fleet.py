"""Class-program calls per round of stage 3: the program's
``stage3/calls`` over ``stage3/assemblies`` (one assembly a round;
repro.sim.fleet.class_work), as it last flushed them to its sinks.  A
round's winners run in each capacity class they fall in as greedy
chunks of its client tiers (the largest tier the remainder fills), one
call a chunk."""
from bench.harness import NothingToRead
from repro.obs import jax_stats


def read(ctx):
    c = getattr(jax_stats, "flushed", {})
    if not c.get("stage3/assemblies"):
        raise NothingToRead("no stage3/ counters flushed by the program")
    return c["stage3/calls"] / c["stage3/assemblies"]
