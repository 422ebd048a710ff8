"""Share of the traced rounds' window in which no operation ran on the
device: 100 x (1 - union of the device op intervals / window), averaged
over the chips (bench/trace.py)."""
from bench.harness import NothingToRead


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or tr.window_s <= 0:
        raise NothingToRead("no reduced device trace")
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
