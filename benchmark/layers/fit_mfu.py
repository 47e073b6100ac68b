"""The whole fit's share of the chips' bf16 peak: featurize and solver
operations from shapes (``ops_count.py``) over ``fit_s`` x chips x peak, in
the traced window.  Bounds every kernel's roofline from above: a later PR
that takes a kernel off the path still has to move this."""


def read(ctx):
    flops = ctx.ops["solver_flops"] + ctx.ops["featurize_flops"]
    seconds = ctx.counters["elapsed"] / ctx.counters["units"]
    return 100.0 * flops / (seconds * ctx.chips * ctx.peaks["bf16_flops_per_s"])
