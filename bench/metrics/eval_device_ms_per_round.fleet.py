"""``eval_device_ms_per_round`` in the fleet cell, read as
bench/metrics/eval_device_ms_per_round.py reads it."""
from bench.metrics.eval_device_ms_per_round import read  # noqa: F401
