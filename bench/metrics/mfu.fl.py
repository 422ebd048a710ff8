"""Model FLOP utilization of the traced rounds: model FLOPs of their real
work (bench/flops.py: three forward passes per sample of every full
minibatch the winners ran, one per test sample of every eval; no padding
rows, no masked steps) over window x chips x the chip's bf16 peak
(bench/peaks.json)."""
from bench.harness import NothingToRead


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not ctx.get("flops_traced"):
        raise NothingToRead("no reduced device trace or no model FLOPs")
    return 100.0 * ctx["flops_traced"] / (
        tr.window_s * ctx["chips"] * ctx["peak_flops"])
