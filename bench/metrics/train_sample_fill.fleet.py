"""Share of the batch-axis slots of stage 3's real steps that carry a
real sample: 100 x real samples / (real steps x the class's batch size),
summed over the class calls (repro.sim.fleet.class_work).  A class that
takes in shards smaller than its batch pads their one step per epoch
under a per-sample mask; this is what that padding leaves.  Read from the
program's ``stage3/`` counters as it last flushed them to its sinks."""
from bench.harness import NothingToRead
from repro.obs import jax_stats


def read(ctx):
    c = getattr(jax_stats, "flushed", {})
    if not c.get("stage3/sample_slots"):
        raise NothingToRead("no stage3/sample_slots counter flushed by "
                            "the program")
    return 100.0 * c["stage3/samples_real"] / c["stage3/sample_slots"]
