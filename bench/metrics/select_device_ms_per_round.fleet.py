"""``select_device_ms_per_round`` in the fleet cell, read as
bench/metrics/select_device_ms_per_round.py reads it."""
from bench.metrics.select_device_ms_per_round import read  # noqa: F401
