"""``setup_compile_s`` in the fleet cell, read as
bench/metrics/setup_compile_s.py reads it."""
from bench.metrics.setup_compile_s import read  # noqa: F401
