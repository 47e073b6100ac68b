"""The unweighted block solver's share of its roofline: the least time the
chip could take for the solver's operations and bytes (``ops_count.
solver_flops/bytes`` at the blocked width, at the published bf16 peak) over
the device time of the program named ``jit__bcd_fit`` — the name exactly:
``_bcd_fit`` is a part of ``_weighted_bcd_fit`` too.  The Gramian and the
cross term multiply at true f32 (six MXU passes) and the program multiplies
out half of each Gramian, so the share is low by design, as
``bcd_roofline`` is, and cannot pass 100%.  Bound by flops (n w^2 over
n w bytes)."""

from benchmark import ops_count
from benchmark.layers import _spans

PROGRAM = "jit__bcd_fit"


def read(ctx):
    device_s = _spans.module_total(ctx, "module_s", lambda name: name == PROGRAM)
    if not device_s:
        return None
    least, _ = ops_count.roofline_seconds(
        ctx.ops["solver_flops"] * ctx.counters["units"],
        ctx.ops["solver_bytes"] * ctx.counters["units"], ctx.peaks, ctx.chips,
    )
    return 100.0 * least / device_s
