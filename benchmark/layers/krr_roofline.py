"""The in-core kernel ridge sweep's share of its roofline: the least time
the chips could take for the algorithm's operations and bytes
(``ops_count_krr.py``, at the published bf16 peak x chips) over the device
time of the programs whose name holds ``krr_fit``.  The sweep multiplies at
true f32 (six MXU passes) and spends a part of each step in exp, Cholesky
and an HBM-bound update, so the share is low by design and cannot pass
100%.  Bound by flops at these shapes (2 n w d over 4 n d bytes a step)."""

from benchmark import ops_count
from benchmark.layers import _spans

PROGRAM = "krr_fit"


def read(ctx):
    device_s = _spans.module_total(ctx, "module_s", lambda name: PROGRAM in name)
    if not device_s:
        return None
    least, _ = ops_count.roofline_seconds(
        ctx.ops["solver_flops"] * ctx.counters["units"],
        ctx.ops["solver_bytes"] * ctx.counters["units"], ctx.peaks, ctx.chips,
    )
    return 100.0 * least / device_s
