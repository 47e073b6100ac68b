"""What the readers of the program's own spans share (no metric of its own:
the harness loads readers by metric name).

The program keeps its closed spans in a bounded ring in memory
(``keystone_tpu.obs.ledger.recent_spans()``) and the readers run in the
program's process.  The window is taken without clock arithmetic: the last
N root spans of the name the metric is about — N whole fits or N scoring
calls were timed, set-up's two come before them, and after the window a fit
cell's check runs applies, never fits, the scoring cell's check calls
nothing, and the references are plain ``jax.numpy`` — and every record that
shares a ``root_id`` with one of them.  Seconds are sums over those records
divided by N: means, so that they add up against ``fit_s``.
"""


def window(ctx, root_name: str, count_key: str):
    """``(records, N)``: the last ``N = ctx.counters[count_key]`` root spans
    named ``root_name`` with all their descendants; None where the program
    keeps no span ring (a parent commit), or the ring holds fewer than N
    such roots, or may have dropped records of the first of them."""
    try:
        from keystone_tpu.obs import ledger

        records, size = ledger.recent_spans(), ledger.RING_SIZE
    except (ImportError, AttributeError):
        return None
    n = int(ctx.counters.get(count_key) or 0)
    roots = [r for r in records if r.parent_id is None and r.name == root_name]
    # a root closes after its descendants: in a ring that has wrapped, only
    # an older root of the same name shows that the window lost none
    if n <= 0 or len(roots) < n + (len(records) >= size):
        return None
    ids = {r.span_id for r in roots[-n:]}
    return [r for r in records if r.root_id in ids], n


def mean_seconds(ctx, root_name: str, count_key: str, name: str):
    """Seconds of the spans ``name`` per root ``root_name``, or None."""
    found = window(ctx, root_name, count_key)
    if found is None:
        return None
    records, n = found
    return sum(r.dur_ns for r in records if r.name == name) / 1e9 / n


def node_device_us_per_unit(ctx, node: str):
    """Device microseconds per unit of work of the programs whose name holds
    the class name ``node``; None where none ran."""
    device_s = module_total(ctx, "module_s", lambda name: node in name)
    return None if device_s is None else 1e6 * device_s / ctx.counters["units"]


def module_total(ctx, field: str, match):
    """Sum of the trace reduction's ``field`` (``module_s``, ``module_runs``)
    over the programs whose name ``match`` accepts; None where none ran."""
    values = [v for name, v in ((ctx.trace or {}).get(field) or {}).items() if match(name)]
    return sum(values) if values else None
