"""Host milliseconds per traced round in the device runtime's plan
assembly (the program's ``cohort/assemble`` spans, repro.sim.runtime
DeviceRuntime.train_cohort), read from an in-memory span sink that only
a traced run attaches."""
from bench.harness import NothingToRead


def read(ctx):
    if not ctx.get("host_assemble_s"):
        raise NothingToRead("no cohort/assemble spans in the traced rounds")
    return 1e3 * ctx["host_assemble_s"] / ctx["rounds_traced"]
