"""Device milliseconds per traced round of stage-3 cohort training: the
device runtime's capacity-class programs (``train`` of repro.sim.engine
CohortEngine._build_train_gather), matched by PATTERN on the trace's
'XLA Modules' line."""
from bench.harness import NothingToRead

PATTERN = r"jit_train$"


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        raise NothingToRead("no reduced device trace")
    s = tr.module_time(PATTERN)
    if s <= 0:
        raise NothingToRead(f"no device program matches {PATTERN!r}; "
                            f"programs seen: {sorted(tr.module_s)}")
    return 1e3 * s / ctx["rounds_traced"]
