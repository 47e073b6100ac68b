"""Seconds of tracing and lowering per timed fit (``jax.monitoring``): a
fit's programs are traced anew in every process and, where the program
keeps no trace cache across fits, in every fit."""


def read(ctx):
    return ctx.compiles_window["trace_lower_seconds"] / ctx.counters["units"]
