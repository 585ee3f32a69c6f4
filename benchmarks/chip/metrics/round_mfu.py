"""Whole round: the runner's useful FLOPs of the traced rounds (for the
fleet scan ``flops.round_useful_flops``: forward and backward of the
scheduled clients' batches, forward of the eval set) over the traced
window, as a share of the chip's bf16 peak."""


def read(ctx):
    peak = ctx.peaks["bf16_flops_per_s"]
    return 100.0 * ctx.useful_flops / ctx.window_s / peak
