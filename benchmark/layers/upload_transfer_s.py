"""Seconds the host link took to bring a fit's or a scoring call's arrays to
the device: the length of the union of the program's ``dataset.transfer``
records — each runs from the start of its put (its parent ``dataset.upload``
span's start) to the moment the array was ready, closed by the program's
watcher thread — per fit or call.  Beside it ``score_upload_host_s`` is the
enqueue alone.

A scoring call's puts (``upload_transfer_s.score``; ``calls`` is a counter of
scoring windows only) are the last N root ``dataset.upload`` spans, made when
the call's ``Dataset`` is, and those under the last N ``pipeline.apply``
roots.  A fit's (``upload_transfer_s.fit``) are the uploads opened after the
previous ``pipeline.fit`` root closed and before its own closed: its data
goes up before ``pipeline.fit`` opens; set-up's fits give the window's first
fit a predecessor, and the check's uploads come after the last fit's root.

A transfer closes AFTER its upload and its root, so it is found by its
``parent_id``, never by its place in the ring.  Nothing to read: no ring, a
ring that lost part of the window, or an upload of the window with no
transfer under it (a parent commit, or one still under way)."""

from benchmark.layers import _spans


def _end(r) -> int:
    return r.t0_ns + r.dur_ns


def _union_ns(spans) -> int:
    total, cursor = 0, 0
    for lo, hi in sorted(spans):
        if hi > cursor:
            total += hi - max(lo, cursor)
            cursor = hi
    return total


def _fit_uploads(ctx, records):
    """The uploads of each of the window's N fits, or None."""
    n = int(ctx.counters.get("units") or 0)
    fits = [r for r in records if r.parent_id is None and r.name == "pipeline.fit"]
    if n <= 0 or len(fits) < n + 1:  # the predecessor shows that the ring lost none
        return None
    uploads = [r for r in records if r.name == "dataset.upload"]
    return [
        [u for u in uploads if _end(before) <= u.t0_ns < _end(fit)]
        for before, fit in zip(fits[-n - 1:-1], fits[-n:])
    ]


def _call_uploads(ctx):
    """The uploads of the window's N calls together, or None."""
    before = _spans.window(ctx, "dataset.upload", "calls")
    inside = _spans.window(ctx, "pipeline.apply", "calls")
    if before is None or inside is None:
        return None
    return [[r for r in before[0] + inside[0] if r.name == "dataset.upload"]]


def read(ctx):
    try:
        from keystone_tpu.obs import ledger

        records = ledger.recent_spans()
    except (ImportError, AttributeError):
        return None
    scoring = "calls" in ctx.counters
    groups = _call_uploads(ctx) if scoring else _fit_uploads(ctx, records)
    if groups is None or not any(groups):
        return None
    spans = {r.parent_id: (r.t0_ns, _end(r)) for r in records if r.name == "dataset.transfer"}
    if any(u.span_id not in spans for group in groups for u in group):
        return None
    total = sum(_union_ns(spans[u.span_id] for u in group) for group in groups)
    return total / 1e9 / int(ctx.counters["calls" if scoring else "units"])
