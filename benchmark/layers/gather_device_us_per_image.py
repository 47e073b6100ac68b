"""Device microseconds per training image of a gather's concatenation: the
program ``jit__gather_concat`` that the executor runs under its
``executor.gather`` span (``workflow/executor.py § _gather``), one a
gather.  A program without that named program (a parent commit, whose
gather is an eager ``jnp.concatenate``) reads nothing."""

from benchmark.layers import _spans

PROGRAM = "jit__gather_concat"


def read(ctx):
    device_s = _spans.module_total(ctx, "module_s", lambda name: name == PROGRAM)
    return None if not device_s else 1e6 * device_s / ctx.counters["units"] / ctx.cell["n"]
