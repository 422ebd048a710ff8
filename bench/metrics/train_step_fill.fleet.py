"""``train_step_fill`` in the fleet cell, read as
bench/metrics/train_step_fill.py reads it."""
from bench.metrics.train_step_fill import read  # noqa: F401
