"""The host's seconds in a scoring call's upload: the program's
``dataset.upload`` spans per call — the staging copy and the enqueue of the
put, not the transfer, which is asynchronous.  A call's images are put when
its ``Dataset`` is made, before ``pipeline.apply`` opens, so the span is a
root of its own; puts inside the call are counted with it."""

from benchmark.layers import _spans


def read(ctx):
    before = _spans.mean_seconds(ctx, "dataset.upload", "calls", "dataset.upload")
    inside = _spans.mean_seconds(ctx, "pipeline.apply", "calls", "dataset.upload")
    return None if before is None or inside is None else before + inside
