"""``stage1_s`` in the fleet cell, read as
bench/metrics/stage1_s.py reads it."""
from bench.metrics.stage1_s import read  # noqa: F401
