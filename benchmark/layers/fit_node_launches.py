"""Executions per fit of the programs that the executor's nodes mint, named
for their classes (``jit_apply_<Class>``, ``jit_fused_<A>_<B>...``), from
the trace's XLA Modules line.  ``fit_launches`` less this and the solver's
programs is what the host dispatches operation by operation."""

from benchmark.layers import _spans

PREFIXES = ("jit_apply_", "jit_fused_")


def read(ctx):
    runs = _spans.module_total(ctx, "module_runs", lambda name: name.startswith(PREFIXES))
    return None if runs is None else runs / ctx.counters["units"]
