"""Local-SGD steps per round that stage 3's class programs run one after
another: each call runs its class's padded step axis once per client
chunk (repro.sim.fleet.class_work).  Read from the program's
process-wide ``stage3/`` counters (repro.obs.jax_stats), which the
device runtime adds to at each cohort assembly while obs records, as it
does through the window of a traced run; the counters as the program
last flushed them to its sinks, over the window's assemblies (one a
round)."""
from bench.harness import NothingToRead
from repro.obs import jax_stats


def read(ctx):
    c = getattr(jax_stats, "flushed", {})
    if not c.get("stage3/assemblies"):
        raise NothingToRead("no stage3/ counters flushed by the program")
    return c["stage3/serial_steps"] / c["stage3/assemblies"]
