"""Programs asked of the compiler (compile requests that go to the
persistent cache, ``jax.monitoring``) per timed fit: the pipeline DSL and
the optimizer decide how many programs a fit is cut into."""


def read(ctx):
    return ctx.compiles_window["requests"] / ctx.counters["units"]
