"""Seconds of stage 1 (``FederatedServer.cluster()``: the clustering
feature pass and the k-means of repro.core.clustering and
repro.kernels.kmeans), host clock ending in block_until_ready on the
labels.  It is part of set-up."""
from bench.harness import NothingToRead


def read(ctx):
    if "stage1_s" not in ctx:
        raise NothingToRead("stage 1 was not timed")
    return ctx["stage1_s"]
