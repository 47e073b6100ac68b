"""The host's seconds inside the nodes of a fit's walk, per fit: the self
time of the program's ``executor.stage`` spans opened directly under
``pipeline.fit`` (a node's span less what its child spans — minting a
program, a solver's dispatch, a forced wait — cover).  The optimizer's
sampling pass runs nodes of its own inside ``pipeline.optimize``; those
seconds are ``fit_optimize_s``'s and are not counted twice."""

from benchmark.layers import _spans


def read(ctx):
    found = _spans.window(ctx, "pipeline.fit", "units")
    if found is None:
        return None
    records, n = found
    from keystone_tpu.obs import ledger

    own = ledger.self_seconds(records)
    walk = [r for r in records if r.name == "executor.stage" and r.parent_id == r.root_id]
    return sum(own[r.span_id] for r in walk) / n
