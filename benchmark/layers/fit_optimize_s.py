"""Seconds a fit spends in the optimizer: the program's ``pipeline.optimize``
spans (the rule batches before the walk, the re-fusion after it) per fit."""

from benchmark.layers import _spans


def read(ctx):
    return _spans.mean_seconds(ctx, "pipeline.fit", "units", "pipeline.optimize")
