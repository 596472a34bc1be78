"""Plain reference: the repo's own patch-embedding CNN for 32x32 RGB.

Not a published architecture: ``TpuCifarCNN`` is this repo's CIFAR CNN
(the reference project's "CIFAR-10 CNN" slot, architecture left free
there). 4x4/4 patch embedding to ``width`` channels -> ReLU -> conv3x3 ->
ReLU -> 2x2 max-pool -> conv3x3 to ``2*width`` -> ReLU -> global average
pool -> dense head; every conv and the head have a bias. Plain
``jax.numpy`` over the two primitives handed in; imports nothing of the
program; names are the program's checkpoint layout.
"""

from __future__ import annotations

import jax.numpy as jnp


def layout(model: dict, input_shape) -> dict:
    w, cin = model["width"], input_shape[-1]
    shapes = {
        "Conv_0": (4, 4, cin, w),
        "Conv_1": (3, 3, w, w),
        "Conv_2": (3, 3, w, 2 * w),
        "Dense_0": (2 * w, model["num_classes"]),
    }
    out = {}
    for name, shape in shapes.items():
        out[(name, "kernel")] = (shape, "kernel")
        out[(name, "bias")] = ((shape[-1],), "zeros")
    return out


def forward(model: dict, params, x, conv, dense):
    def layer(h, name, stride, padding):
        p = params[name]
        return jnp.maximum(conv(h, p["kernel"], stride, padding) + p["bias"], 0.0)

    h = layer(x, "Conv_0", 4, "VALID")
    h = layer(h, "Conv_1", 1, "SAME")
    b, hh, ww, c = h.shape
    h = h.reshape(b, hh // 2, 2, ww // 2, 2, c).max(axis=(2, 4))
    h = layer(h, "Conv_2", 1, "SAME")
    h = jnp.mean(h, axis=(1, 2))
    head = params["Dense_0"]
    return dense(h, head["kernel"]) + head["bias"]
