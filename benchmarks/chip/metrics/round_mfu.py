"""Whole round: useful FLOPs of the traced rounds (``flops.
round_useful_flops``: forward and backward of the scheduled clients'
batches, forward of the eval set) over the traced window, as a share of
the chip's bf16 peak."""
from chipbench import flops


def read(ctx):
    cfg = ctx.cell.config
    t = cfg["training"]
    n_test = cfg["data"]["n_test"] if ctx.cell.traffic["with_eval"] else 0
    useful = sum(flops.round_useful_flops(cfg["model"], t["tau"],
                                          t["batch_size"], int(n), n_test)
                 for n in ctx.scheduled)
    return 100.0 * useful / ctx.window_s / ctx.peaks["bf16_flops_per_s"]
