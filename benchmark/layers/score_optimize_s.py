"""Seconds a scoring call spends in the optimizer: the program's
``pipeline.optimize`` spans under ``pipeline.apply`` per call (a call
optimizes its graph again every time)."""

from benchmark.layers import _spans


def read(ctx):
    return _spans.mean_seconds(ctx, "pipeline.apply", "calls", "pipeline.optimize")
