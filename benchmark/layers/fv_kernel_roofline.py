"""The fused PCA + Fisher-vector kernels' share of their roofline: the least
time the chip could take for their operations and bytes (``ops_count.py §
fv_kernel_per_image``, both branches, x images in the window) over the
device time of the Pallas custom calls, found in the trace by ``KERNEL``.
Says nothing where no such operation ran (a later PR that takes the kernel
off the path leaves this silent; ``score_mfu`` still bounds it)."""

from benchmark import ops_count

KERNEL = ("fisher", "fused_forward", "fv_")


def read(ctx):
    ops = (ctx.trace or {}).get("op_s") or {}
    device_s = sum(s for name, s in ops.items() if any(k in name.lower() for k in KERNEL))
    if device_s <= 0:
        return None
    images = ctx.counters["units"]
    least, _ = ops_count.roofline_seconds(
        ctx.ops["fv_kernel_flops"] * images, ctx.ops["fv_kernel_bytes"] * images,
        ctx.peaks, ctx.chips,
    )
    return 100.0 * least / device_s
