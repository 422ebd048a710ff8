"""Share of the client-step slots of stage 3's class calls that carry a
real step: 100 x unmasked steps / (tier x padded step axis), summed over
the calls (repro.sim.fleet.class_work).  Read from the program's
process-wide ``stage3/`` counters (repro.obs.jax_stats), which the
device runtime adds to at each cohort assembly while obs records, as it
does through the window of a traced run; the counters as the program
last flushed them to its sinks."""
from bench.harness import NothingToRead
from repro.obs import jax_stats


def read(ctx):
    c = getattr(jax_stats, "flushed", {})
    if not c.get("stage3/step_slots"):
        raise NothingToRead("no stage3/ counters flushed by the program")
    return 100.0 * c["stage3/steps_real"] / c["stage3/step_slots"]
