"""The fused featurizer's share of its roofline: the least time the chip
could take for the convolution's operations and bytes (``ops_count_cifar.
conv_per_image`` through the adapter's ``ops()``: the gemm of every patch
with every filter, the box sums, rectifier and pooling; the image in, the
pooled features out, the filters once a pass; at the published bf16 peak)
over the device time of the programs whose name holds ``Convolver``.  Bound
by flops (1.57 GF against 0.32 MB an image).  The count is of the
algorithm, not of the program: padding rows, a second pass or a wider
stream only lower the share, and it cannot pass 100%."""

from benchmark import ops_count
from benchmark.layers import _spans

NODE = "Convolver"


def read(ctx):
    device_s = _spans.module_total(ctx, "module_s", lambda name: NODE in name)
    if not device_s or "conv_flops" not in ctx.ops:
        return None
    least, _ = ops_count.roofline_seconds(
        ctx.ops["conv_flops"] * ctx.counters["units"],
        ctx.ops["conv_bytes"] * ctx.counters["units"], ctx.peaks, ctx.chips,
    )
    return 100.0 * least / device_s
