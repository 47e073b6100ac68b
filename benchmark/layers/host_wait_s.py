"""Seconds the host stood still in forced waits, per fit or per scoring call:
the program's ``device.wait`` spans under the last N ``pipeline.fit`` roots
(``host_wait_s.fit``: the seconds ``fit()`` waited for the device before it
had dispatched all its work — a ``Cacher``'s sync under an
``executor.stage`` of the walk, the sampling pass's syncs under
``optimizer.rule``, a solver's flow control under ``solver.fit``) or under
the last N ``pipeline.apply`` roots (``host_wait_s.score``; ``calls`` is a
counter of scoring windows only).  The caller's own wait after ``fit()``
returned and the readback's are no ``device.wait`` and are not in it.  A
program that has no name for these waits (a parent commit) reads nothing: 0
is what a fit without a sync reads."""

from benchmark.layers import _spans


def read(ctx):
    try:  # a program that names its waits
        from keystone_tpu.obs.ledger import waiting  # noqa: F401
    except ImportError:
        return None
    root, count = ("pipeline.apply", "calls") if "calls" in ctx.counters else (
        "pipeline.fit", "units")
    return _spans.mean_seconds(ctx, root, count, "device.wait")
