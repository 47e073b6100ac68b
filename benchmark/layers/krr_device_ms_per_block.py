"""Device milliseconds per block step of the in-core kernel ridge sweep:
the device time of the programs whose name holds ``krr_fit`` over
fits x epochs x blocks (one column block, one block solve and one update
of F a step)."""

from benchmark.layers import _spans
from benchmark.layers.krr_roofline import PROGRAM


def read(ctx):
    us_per_fit = _spans.node_device_us_per_unit(ctx, PROGRAM)
    if not us_per_fit:
        return None
    epochs = ctx.cell.get("num_epochs", ctx.cfg["num_epochs"])
    blocks = -(-ctx.cell["n"] // ctx.cfg["block_size"])
    return 1e-3 * us_per_fit / (epochs * blocks)
