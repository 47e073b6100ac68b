"""The weighted BCD programs' share of their roofline: the least time the
chips could take for the solver's operations and bytes (``ops_count.py``,
at the published bf16 peak x chips) over the device time of the programs
whose name holds ``weighted_bcd_fit``.  The solver multiplies at true f32,
which is several MXU passes, so the share is low by design and cannot pass
100%.  Bound by flops at these shapes (n w^2 over n w bytes)."""

from benchmark import ops_count

PROGRAM = "weighted_bcd_fit"


def read(ctx):
    modules = (ctx.trace or {}).get("module_s") or {}
    device_s = sum(s for name, s in modules.items() if PROGRAM in name)
    if device_s <= 0:
        return None
    least, _ = ops_count.roofline_seconds(
        ctx.ops["solver_flops"] * ctx.counters["units"],
        ctx.ops["solver_bytes"] * ctx.counters["units"], ctx.peaks, ctx.chips,
    )
    return 100.0 * least / device_s
