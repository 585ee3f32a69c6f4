"""Operations the work needs, counted from shapes.

Kept with the benchmark so that no later change to the program can move
the yardstick: the CNN's forward FLOPs come from the configuration's
layer list, not from ``repro.models``.
"""
from __future__ import annotations

LANES = 128


def cnn_forward_flops(model: dict) -> int:
    """Multiply-add FLOPs (2 per MAC) of one sample's forward pass:
    stride-1 SAME convolutions at the pre-pool resolution, the dense
    hiddens and the head. Bias, ReLU, pooling and softmax are left out."""
    hw, cin, k = model["in_hw"], model["in_ch"], model["kernel"]
    total = 0
    for cout in model["conv_channels"]:
        total += 2 * hw * hw * k * k * cin * cout
        hw, cin = hw // 2, cout
    if model["extra_pool"]:
        hw //= 2
    dim = hw * hw * cin
    for h in list(model["hidden"]) + [model["n_classes"]]:
        total += 2 * dim * h
        dim = h
    return total


def round_useful_flops(model: dict, tau: int, batch: int, n_scheduled: int,
                       n_test: int) -> int:
    """One round's useful work: forward + backward (3x forward) of tau
    batches on each scheduled client, and the forward pass of the eval
    set. Padding slots of the active set are waste and do not count."""
    fwd = cnn_forward_flops(model)
    return 3 * fwd * tau * batch * n_scheduled + fwd * n_test


def pad_len(z: int, block_m: int) -> int:
    """Wire length: z rounded up to whole (block_m x 128-lane) tiles."""
    tile = block_m * LANES
    return -(-z // tile) * tile
