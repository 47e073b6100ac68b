"""Device microseconds per image of the programs that run the LCS branch:
those whose name holds the node's class.  With ``fv_kernel_roofline`` and
``sift_device_us_per_image`` each of the three featurize stages has a
metric."""

from benchmark.layers import _spans


def read(ctx):
    return _spans.node_device_us_per_unit(ctx, "LCSExtractor")
