"""``jax.jit`` wrappers minted per fit: the program's
``transformer.jit_mint`` spans, one for each node program that a fit traces
and lowers anew (which node is in the span's ``node``)."""

from benchmark.layers import _spans


def read(ctx):
    found = _spans.window(ctx, "pipeline.fit", "units")
    if found is None:
        return None
    records, n = found
    return sum(r.name == "transformer.jit_mint" for r in records) / n
