"""Experiment: the expert layer's ragged form alone, at the Trinity cell's
shapes: how a pass's device time splits between moving the tokens to and
from the rows and the three products.

    python scripts/exp_expert_dispatch.py [--root <checkout>] [--trace DIR]

Shapes come from the cell's configuration (``BENCHMARK.json`` ->
``trinity_mini_26b_l5_ep8_vp8``): 8,192 tokens (one sequence a step),
hidden 2,048, experts of 1,024, 16 held of 128, top-8, bf16 products;
``lm_parts.route`` + ``lm_parts.experts`` of the checkout ``--root`` names
(the parent's, unpacked with ``git archive``, or this one), with weights
and tokens drawn from ``--seed`` as the model's are (router N(0, 1/D), the
selection bias N(0, 0.01^2), the experts N(0, 1/fan-in), RMS-normed f32
tokens), the parameters in bf16 as a client step holds them. Printed, one
JSON object: the forward and the forward + backward (the gradients of the
tokens, the router and the three kernels) in ms, medians over ``--iters``
calls each ended by a device fetch; the routing's own numbers
(assignments a token, overflow); and the norm, largest magnitude and
finiteness of the output and of each gradient, by which two checkouts'
results are compared. ``--nan_check`` adds :func:`nan_check`.

``--trace DIR`` profiles a few calls of each and prints the device time
of a pass split by what its ops do: the sort, scatters, gathers, the
ragged products, other products, and the rest (masks, casts, f32 passes),
each op classed by the instructions of its fusion in the compiled module.

Imported by neither the package nor the benchmark.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CELL_CONFIG = "trinity_mini_26b_l5_ep8_vp8"
#: What an op's fusion holds decides its class, first match wins.
CLASSES = (
    ("ragged_products", ("ragged-dot",)),
    ("sort", ("sort",)),
    ("scatter", ("scatter",)),
    ("gather", ("gather",)),
    ("products", ("convolution", "dot")),
)


def shapes_of_cell(tokens: int = 8192) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["configs"] if c["name"] == CELL_CONFIG]
    with open(os.path.join(ROOT, entry["file"])) as f:
        c = json.load(f)
    return dict(
        N=tokens, D=c["hidden_size"], F=c["moe_intermediate_size"],
        E=c["published"]["num_experts"], held=c["num_experts"],
        K=c["num_experts_per_tok"], scale=c["route_scale"])


def build(parts, s):
    import jax
    import jax.numpy as jnp

    capacity = parts.expert_capacity(s["N"], s["K"], s["E"])

    def layer(p, x):
        combine = parts.route(
            p["router"], x, top_k=s["K"], expert_offset=0,
            experts_held=s["held"], bias=p["bias"], eps=1e-20,
            scale=s["scale"])
        return parts.experts(p, x, combine, capacity=capacity,
                             dtype=jnp.bfloat16)

    def forward(p, x):
        return layer(p, x)[0]

    def backward(p, x, ct):
        trained = {k: v for k, v in p.items() if k != "bias"}
        return jax.grad(
            lambda t, x: jnp.vdot(forward({**p, **t}, x), ct),
            argnums=(0, 1))(trained, x)

    return jax.jit(forward), jax.jit(backward), jax.jit(layer), capacity


def draw(s, seed: int):
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.key(seed), 7)
    D, F, held = s["D"], s["F"], s["held"]

    def normal(key, shape, std, dtype=jnp.bfloat16):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    p = {"router": normal(keys[0], (D, s["E"]), D ** -0.5),
         "bias": normal(keys[1], (s["E"],), 0.01),
         "gate": normal(keys[2], (held, D, F), D ** -0.5),
         "up": normal(keys[3], (held, D, F), D ** -0.5),
         "down": normal(keys[4], (held, F, D), F ** -0.5)}
    x = jax.random.normal(keys[5], (s["N"], D), jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
    return p, x, normal(keys[6], (s["N"], D), 1.0, jnp.float32)


def median_ms(fn, args, iters: int) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(times)


def classes_of(text: str) -> dict:
    """``{op name: class}`` of a compiled module's top-level ops."""
    sys.path.insert(0, HERE)
    from compile_for_v5e import parse_module

    instructions = parse_module(text)
    held = collections.defaultdict(set)
    for i in instructions.values():
        held[i["computation"]].add(i["opcode"])

    def opcodes(i, depth=0):
        found = {i["opcode"]}
        if i["calls"] and depth < 3:
            for op in held[i["calls"]]:
                found.add(op)
            for j in instructions.values():
                if j["computation"] == i["calls"] and j["calls"]:
                    found |= opcodes(j, depth + 1)
        return found

    out = {}
    for name, i in instructions.items():
        if i["fused"] or i["opcode"] in ("conditional", "while", "call"):
            continue  # inside a fusion, or a frame around other ops
        found = " ".join(opcodes(i) | {name})
        out[name] = next((c for c, keys in CLASSES
                          if any(k in found for k in keys)), "other")
        out[name] = (out[name], i["shape"].split("{")[0][:60])
    return out


def device_ms_by_class(trace_dir: str, classes: dict, calls: int) -> dict:
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    totals, ops = collections.Counter(), collections.Counter()
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                m = re.match(r"%?(\S+) = ", ev.name)
                name = m.group(1) if m else ev.name
                if name not in classes:
                    continue  # a frame (while, conditional) or elsewhere
                totals[classes[name][0]] += ev.duration_ns / 1e6
                ops[name] += ev.duration_ns / 1e6
    out = {k: round(v / calls, 4) for k, v in sorted(totals.items())}
    out["top_ops"] = [[name, *classes[name], round(ms / calls, 4)]
                      for name, ms in ops.most_common(12)]
    return out


def summary(tree) -> dict:
    """Norm, largest magnitude and finiteness of each leaf: what two
    checkouts' results are compared by (the arrays do not come back)."""
    import jax
    import jax.numpy as jnp

    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        leaf = leaf.astype(jnp.float32)
        out[jax.tree_util.keystr(path)] = [
            float(jnp.linalg.norm(leaf.reshape(-1))),
            float(jnp.max(jnp.abs(leaf))), bool(jnp.all(jnp.isfinite(leaf)))]
    return out


def nan_check() -> dict:
    """What the TPU's ragged product does with rows past the last group
    that hold NaN: in the rows of the product (its forward and its
    gradients), and in the cotangent of its result. ``True`` = every
    number that belongs to a group came out finite and equal to the
    product over the rows zeroed."""
    import jax
    import jax.numpy as jnp

    rows, D, F, sizes = 2048, 256, 512, jnp.array([300, 0, 517, 183])
    keys = jax.random.split(jax.random.key(0), 3)
    real = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
    x = jax.random.normal(keys[0], (rows, D), jnp.bfloat16)
    w = jax.random.normal(keys[1], (4, D, F), jnp.bfloat16)
    ct = jax.random.normal(keys[2], (rows, F), jnp.float32)

    def product(x, w):
        return jax.lax.ragged_dot(x, w, sizes,
                                  preferred_element_type=jnp.float32)

    def both(x, ct):
        y, vjp = jax.vjp(product, x, w)
        dx, dw = vjp(ct)
        return jnp.where(real, y, 0), jnp.where(real, dx, 0), dw

    clean = jax.jit(both)(jnp.where(real, x, 0), jnp.where(real, ct, 0))
    dirty = jax.jit(both)(jnp.where(real, x, jnp.nan),
                          jnp.where(real, ct, jnp.nan))
    return {name: bool(jnp.all(jnp.isfinite(d)) and jnp.allclose(c, d))
            for name, c, d in zip(("y", "dx", "dw"), clean, dirty)}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--root", default=ROOT)
    parser.add_argument("--seed", type=int, default=2147483811)
    parser.add_argument("--iters", type=int, default=30)
    parser.add_argument("--trace", help="profile into this directory")
    parser.add_argument("--tokens", type=int, default=8192,
                        help="tokens a pass (the cell's: 8,192)")
    parser.add_argument("--nan_check", action="store_true",
                        help="also run nan_check() (on the chip)")
    args = parser.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.root))
    import jax
    import jax.numpy as jnp

    from distributed_learning_simulator_tpu.models import lm_parts as parts

    s = shapes_of_cell(args.tokens)
    forward, backward, layer, capacity = build(parts, s)
    label = {"root": os.path.relpath(os.path.abspath(args.root), ROOT),
             **s, "capacity": capacity}
    p, x, ct = draw(s, args.seed)
    y, load, overflow = layer(p, x)
    out = {**label, "platform": jax.devices()[0].platform,
           "device_kind": jax.devices()[0].device_kind,
           "assignments_per_token": float(jnp.sum(load)) / s["N"],
           "overflow": bool(overflow),
           "forward_ms": median_ms(forward, (p, x), args.iters),
           "forward_backward_ms": median_ms(backward, (p, x, ct),
                                            args.iters)}
    out["output"] = summary({"y": y, "load": load})
    out["gradients"] = summary(backward(p, x, ct))
    if args.nan_check:
        out["nan_check"] = nan_check()
    if args.trace:
        calls = 5
        for name, fn, fargs in (("forward", forward, (p, x)),
                                ("forward_backward", backward, (p, x, ct))):
            where = os.path.join(args.trace, name)
            classes = classes_of(fn.lower(*fargs).compile().as_text())
            jax.block_until_ready(fn(*fargs))
            with jax.profiler.trace(where):
                for _ in range(calls):
                    jax.block_until_ready(fn(*fargs))
            out[f"{name}_device_ms_by_class"] = device_ms_by_class(
                where, classes, calls)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
