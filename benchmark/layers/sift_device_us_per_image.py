"""Device microseconds per image of the programs that run the SIFT branch:
those whose name holds the node's class (today the fused chain
``jit_fused_PixelScaler_GrayScaler_SIFTExtractor``)."""

from benchmark.layers import _spans


def read(ctx):
    return _spans.node_device_us_per_unit(ctx, "SIFTExtractor")
