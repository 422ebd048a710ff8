"""``mfu.fl`` in the fleet cell, read as bench/metrics/mfu.fl.py
reads it."""
from bench.harness import BENCH, load_module

read = load_module(BENCH / "metrics" / "mfu.fl.py").read
