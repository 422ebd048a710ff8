"""``retraces_in_window`` in the fleet cell, read as
bench/metrics/retraces_in_window.py reads it."""
from bench.metrics.retraces_in_window import read  # noqa: F401
