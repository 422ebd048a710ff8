"""Seconds in which JAX traced, lowered and compiled programs or loaded
them from the persistent cache: the program's ``compile_s`` counter
(repro.obs.jax_stats: the union of the intervals of JAX's compile
events), as the program last flushed it to its sinks.  A traced run
flushes at the end of its window, before the check compiles anything of
its own, and the window compiles nothing (``retraces_in_window``): so it
reads set-up's compiles."""
from bench.harness import NothingToRead
from repro.obs import jax_stats


def read(ctx):
    value = getattr(jax_stats, "flushed", {}).get("compile_s")
    if value is None:
        raise NothingToRead("no compile_s counter flushed by the program")
    return value
