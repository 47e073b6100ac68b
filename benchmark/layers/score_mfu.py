"""The whole scoring step's share of the chip's bf16 peak: featurize and
scoring operations of one image from shapes (``ops_count.py``) x images per
second in the traced window, over chips x peak."""


def read(ctx):
    per_image = ctx.ops["featurize_flops"] + ctx.ops["scoring_flops"]
    rate = ctx.counters["units"] / ctx.counters["elapsed"]
    return 100.0 * per_image * rate / (ctx.chips * ctx.peaks["bf16_flops_per_s"])
