"""``device_idle_share.fl`` in the fleet cell, read as
bench/metrics/device_idle_share.fl.py reads it."""
from bench.harness import BENCH, load_module

read = load_module(BENCH / "metrics" / "device_idle_share.fl.py").read
