"""Program executions on the first device per fit, from the trace's XLA
Modules line.  Equal from fit to fit is the sign that no memo answered for
a fit."""


def read(ctx):
    runs = (ctx.trace or {}).get("module_runs")
    if not runs:
        return None
    return sum(runs.values()) / ctx.counters["units"]
