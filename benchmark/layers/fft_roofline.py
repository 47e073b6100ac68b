"""The random-FFT featurizer's share of its roofline: the least time the
chip could take for the transforms' operations and bytes (``ops_count_mnist.
fft_per_image`` through the adapter's ``ops()``: a 1024-point real FFT's
2.5 N log2 N a branch; 784 float32 in and 512 out a branch and image; at
the published bf16 peak and HBM bandwidth) over the device time of the
programs whose name holds ``PaddedFFT`` (today ``jit_fused_RandomSignNode_
PixelScaler_PaddedFFT_LinearRectifier``: sign, scaling, transform and
rectifier in one).  Bound by bytes.  The count is of the algorithm, not of
the program: a wider stream, a second pass or a transform done as a product
only lower the share.  The program reads the pixels as uint8, 784 bytes
where the count has 3,136, so the share could pass 100% only once that
program ran at 55% of its own least time (it reads 7%, PERF.md section 5)."""

from benchmark import ops_count
from benchmark.layers import _spans

NODE = "PaddedFFT"


def read(ctx):
    device_s = _spans.module_total(ctx, "module_s", lambda name: NODE in name)
    if not device_s or "fft_flops" not in ctx.ops:
        return None
    least, _ = ops_count.roofline_seconds(
        ctx.ops["fft_flops"] * ctx.counters["units"],
        ctx.ops["fft_bytes"] * ctx.counters["units"], ctx.peaks, ctx.chips,
    )
    return 100.0 * least / device_s
