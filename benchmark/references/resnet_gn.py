"""Plain reference: basic-block ResNet with GroupNorm for 32x32 inputs.

He et al. 2015 (Table 1, basic blocks) in the usual CIFAR adaptation (3x3
stem, no max-pool), GroupNorm (Wu & He 2018) in place of BatchNorm as
federated work requires (Hsieh et al. 2020): stem conv3x3 -> GN -> ReLU,
stages of ``stage_sizes`` blocks at widths w, 2w, 4w, 8w (the first block
of every stage after the first has stride 2 and a 1x1-conv + GN shortcut),
global average pool, dense head. A block is conv3x3 -> GN -> ReLU ->
conv3x3 -> GN, added to its shortcut, ReLU. No conv has a bias.

Straightforward ``jax.numpy``: every product goes through the two
primitives handed in (``conv``, ``dense``), which fix the precision, so
the same text is the f32 ``highest`` reference and its lower-precision
control. Nothing of the program is imported. The parameter names are the
program's checkpoint layout (a format, like a file's field names): the
comparison needs to know which tensor of a checkpoint is which layer.
"""

from __future__ import annotations

import jax.numpy as jnp

GN_EPS = 1e-6  # the program's GroupNorm epsilon (flax's default)


def _blocks(model: dict):
    """(path of the block, cin, cout, stride, key names) in forward order."""
    width = model["width"]
    # The program folds column pairs into channels for the 64-wide first
    # stage of even-sized inputs and names those blocks differently; the
    # tensors are the ordinary [3, 3, cin, cout] kernels either way.
    folded = width == 64
    plain = dict(conv1=("Conv_0", "kernel"), gn1=("GroupNorm_0",),
                 conv2=("Conv_1", "kernel"), gn2=("GroupNorm_1",),
                 proj=("Conv_2", "kernel"), gnp=("GroupNorm_2",))
    out, n_plain, cin = [], 0, width
    for stage, n_blocks in enumerate(model["stage_sizes"]):
        cout = width * 2 ** stage
        for b in range(n_blocks):
            stride = 2 if stage > 0 and b == 0 else 1
            if folded and stage == 0:
                name = f"FoldedResidualBlock_{b}"
                keys = dict(conv1=("FoldedConv3x3_0", "kernel"),
                            gn1=("FoldedGroupNorm_0",),
                            conv2=("FoldedConv3x3_1", "kernel"),
                            gn2=("FoldedGroupNorm_1",))
            elif folded and stage == 1 and b == 0:
                name = "FoldedTransitionBlock_0"
                keys = dict(conv1=("conv1_kernel",), gn1=("GroupNorm_0",),
                            conv2=("Conv_0", "kernel"), gn2=("GroupNorm_1",),
                            proj=("proj_kernel",), gnp=("GroupNorm_2",))
            else:
                name = f"ResidualBlock_{n_plain}"
                n_plain += 1
                keys = plain
            out.append((name, cin, cout, stride, keys))
            cin = cout
    return out


def _groups(model: dict, c: int) -> int:
    return min(model.get("groups", 32), c)


def layout(model: dict, input_shape) -> dict:
    """``{path: (shape, kind)}`` of every parameter, ``kind`` one of
    ``kernel`` (fan-in scaled normal), ``ones``, ``zeros``."""
    out = {}

    def gn(path, c):
        out[path + ("scale",)] = ((c,), "ones")
        out[path + ("bias",)] = ((c,), "zeros")

    width = model["width"]
    out[("Conv_0", "kernel")] = ((3, 3, input_shape[-1], width), "kernel")
    gn(("GroupNorm_0",), width)
    for name, cin, cout, stride, keys in _blocks(model):
        out[(name,) + keys["conv1"]] = ((3, 3, cin, cout), "kernel")
        gn((name,) + keys["gn1"], cout)
        out[(name,) + keys["conv2"]] = ((3, 3, cout, cout), "kernel")
        gn((name,) + keys["gn2"], cout)
        if stride != 1 or cin != cout:
            out[(name,) + keys["proj"]] = ((1, 1, cin, cout), "kernel")
            gn((name,) + keys["gnp"], cout)
    c_last = width * 2 ** (len(model["stage_sizes"]) - 1)
    out[("Dense_0", "kernel")] = ((c_last, model["num_classes"]), "kernel")
    out[("Dense_0", "bias")] = ((model["num_classes"],), "zeros")
    return out


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def group_norm(x, scale, bias, groups: int):
    b, h, w, c = x.shape
    xg = x.reshape(b, h, w, groups, c // groups)
    mean = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) / jnp.sqrt(var + GN_EPS)
    return xg.reshape(b, h, w, c) * scale + bias


def forward(model: dict, params, x, conv, dense):
    """Logits ``[B, classes]`` for images ``x`` ``[B, H, W, C]`` in [0, 1].

    ``conv(x, kernel, stride, padding)`` and ``dense(x, kernel)`` are the
    only places numbers are multiplied together."""

    def gn(h, path):
        p = _get(params, path)
        return group_norm(h, p["scale"], p["bias"], _groups(model, h.shape[-1]))

    h = conv(x, _get(params, ("Conv_0", "kernel")), 1, "SAME")
    h = jnp.maximum(gn(h, ("GroupNorm_0",)), 0.0)
    for name, cin, cout, stride, keys in _blocks(model):
        blk = params[name]
        y = conv(h, _get(blk, keys["conv1"]), stride, "SAME")
        y = jnp.maximum(gn(y, (name,) + keys["gn1"]), 0.0)
        y = conv(y, _get(blk, keys["conv2"]), 1, "SAME")
        y = gn(y, (name,) + keys["gn2"])
        if stride != 1 or cin != cout:
            h = conv(h, _get(blk, keys["proj"]), stride, "VALID")
            h = gn(h, (name,) + keys["gnp"])
        h = jnp.maximum(y + h, 0.0)
    h = jnp.mean(h, axis=(1, 2))
    head = params["Dense_0"]
    return dense(h, head["kernel"]) + head["bias"]
