"""Device milliseconds per traced round of eval: the fused accuracy and
loss program (``_eval`` of repro.core.server FederatedServer), matched by
PATTERN on the trace's 'XLA Modules' line."""
from bench.harness import NothingToRead

PATTERN = r"jit__eval$"


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        raise NothingToRead("no reduced device trace")
    s = tr.module_time(PATTERN)
    if s <= 0:
        raise NothingToRead(f"no device program matches {PATTERN!r}; "
                            f"programs seen: {sorted(tr.module_s)}")
    return 1e3 * s / ctx["rounds_traced"]
