"""Device microseconds per training image of the programs that run the
convolution: those whose name holds ``Convolver`` (today
``jit_apply_PooledConvolver``, convolution through pooling in one)."""

from benchmark.layers import _spans
from benchmark.layers.conv_roofline import NODE


def read(ctx):
    us_per_fit = _spans.node_device_us_per_unit(ctx, NODE)
    return None if not us_per_fit else us_per_fit / ctx.cell["n"]
