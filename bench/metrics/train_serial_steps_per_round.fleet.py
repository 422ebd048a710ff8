"""``train_serial_steps_per_round`` in the fleet cell, read as
bench/metrics/train_serial_steps_per_round.py reads it."""
from bench.metrics.train_serial_steps_per_round import read  # noqa: F401
