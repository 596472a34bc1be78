"""FLOPs one training sample needs in the patch-embedding CNN, from
shapes: multiply-adds of the three convolutions and the head (2 FLOPs
each), forward once and backward twice. Bias adds, ReLU, pooling, loss
and optimizer are not counted, nor the server's evaluation."""

from __future__ import annotations


def forward_macs(model: dict, input_shape) -> int:
    h, w, c = input_shape
    width = model["width"]
    h, w = h // 4, w // 4
    macs = h * w * 4 * 4 * c * width          # 4x4/4 patch embedding
    macs += h * w * 3 * 3 * width * width     # conv3x3
    h, w = h // 2, w // 2                     # 2x2 max-pool
    macs += h * w * 3 * 3 * width * 2 * width  # conv3x3 to 2*width
    return macs + 2 * width * model["num_classes"]


def train_flops_per_sample(model: dict, input_shape) -> int:
    return 3 * 2 * forward_macs(model, input_shape)
