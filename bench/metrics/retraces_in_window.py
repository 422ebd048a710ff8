"""Traces (compilations) of the program's instrumented jitted bodies
during the measured window: the movement of repro.obs.jax_stats'
``traces`` counter.  Warm-up should leave nothing to trace: it reads 0."""
from bench.harness import NothingToRead


def read(ctx):
    if "retraces_in_window" not in ctx:
        raise NothingToRead("no trace counter")
    return ctx["retraces_in_window"]
