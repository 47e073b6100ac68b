"""1 - busy union over the traced window, averaged over the chips."""

from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.idle_pct(ctx.trace)
