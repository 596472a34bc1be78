"""FLOPs one training sample needs in the GroupNorm ResNet, from shapes.

Counts the multiply-adds of the convolutions and the dense head (2 FLOPs
each) of the published network on the unfolded shapes: forward once,
backward twice (gradients with respect to inputs and to weights).
GroupNorm, ReLU, the loss and the optimizer are not counted (under 1 %),
nor is anything an implementation adds: zero taps of a packed kernel,
recomputation, the server's evaluation.
"""

from __future__ import annotations


def conv_macs(h: int, w: int, kh: int, kw: int, cin: int, cout: int,
              stride: int = 1) -> int:
    """Multiply-adds of a SAME-padded (or exactly tiling) convolution."""
    ho, wo = -(-h // stride), -(-w // stride)
    return ho * wo * kh * kw * cin * cout


def dense_macs(cin: int, cout: int) -> int:
    return cin * cout


def forward_macs(model: dict, input_shape) -> int:
    h, w, c = input_shape
    width = model["width"]
    macs = conv_macs(h, w, 3, 3, c, width)
    cin = width
    for stage, n_blocks in enumerate(model["stage_sizes"]):
        cout = width * 2 ** stage
        for b in range(n_blocks):
            stride = 2 if stage > 0 and b == 0 else 1
            macs += conv_macs(h, w, 3, 3, cin, cout, stride)
            if stride != 1 or cin != cout:
                macs += conv_macs(h, w, 1, 1, cin, cout, stride)
            h, w = -(-h // stride), -(-w // stride)
            macs += conv_macs(h, w, 3, 3, cout, cout)
            cin = cout
    return macs + dense_macs(cin, model["num_classes"])


def train_flops_per_sample(model: dict, input_shape) -> int:
    return 3 * 2 * forward_macs(model, input_shape)
