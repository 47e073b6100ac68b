"""Device microseconds per training image of the programs that run the
random-FFT featurizer: those whose name holds ``PaddedFFT`` (today
``jit_fused_RandomSignNode_PixelScaler_PaddedFFT_LinearRectifier``, one a
branch)."""

from benchmark.layers import _spans
from benchmark.layers.fft_roofline import NODE


def read(ctx):
    us_per_fit = _spans.node_device_us_per_unit(ctx, NODE)
    return None if not us_per_fit else us_per_fit / ctx.cell["n"]
