"""Experiment: the gated delta rule with ONE decay a head alone, at the Olmo
cell's shapes: what a forward and a forward + backward pass take on the
device, and how that time splits between the chunk-local work and the
sequential walk over the chunks.

    python scripts/exp_delta_rule.py [--root <checkout>] [--forms xla,auto]
                                     [--trace DIR]

Shapes come from the cell's configuration (``BENCHMARK.json`` ->
``olmo_hybrid_7b_l4_vp8``): one sequence of 4,096 positions, 30 heads,
keys of 96, values of 192, products with the state in bf16. Inputs are
drawn from ``--seed`` as the model makes them (``q``, ``k`` l2-normed,
``q`` over ``sqrt(d_k)``; the log decay ``-softplus``; ``beta = 2
sigmoid``). ``--forms`` names what is timed: ``auto`` is
``lm_parts.chunked_delta_rule`` of the checkout ``--root`` names, in the
form it chooses; ``xla`` its XLA form (``lm_parts.chunked_delta_rule_xla``
where the checkout has it, else ``chunked_delta_rule``, which is then the
XLA form). Printed, one JSON object: per form the forward and the forward
+ backward (the gradients of all five inputs) in ms, medians over
``--iters`` calls each ended by a device fetch; the norm, largest
magnitude and finiteness of the output and of each gradient, by which two
checkouts are compared; and each form's relative distance from the first
form's output and gradients. ``--variants 1x6,2x6`` also times the
kernels at those values of ``ops/delta_rule.STACK`` x ``HEAD_BLOCK``.

``--trace DIR`` profiles a few calls of each and prints the device time a
call split by where its ops run: ``scan`` (inside a ``while`` loop: the
chunk after chunk walk of the XLA form, forward or backward), ``kernel``
(the Pallas kernels, ``delta_rule_forward`` / ``delta_rule_backward``),
``chunk_local`` (everything else: ``A``, ``P``, the triangular inverse,
``W``, ``U0``, the decays, the layout changes).

Imported by neither the package nor the benchmark.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
from compile_for_v5e import _COMPUTATION  # noqa: E402
from exp_expert_dispatch import median_ms, summary  # noqa: E402

CELL_CONFIG = "olmo_hybrid_7b_l4_vp8"
KERNELS = ("delta_rule_forward", "delta_rule_backward")
FRAMES = ("while", "conditional", "call")
_CALLED = re.compile(
    r"(?:body|condition|calls|to_apply|branch_computations)=\{?([^,}\s]+)")


def shapes_of_cell(positions: int = 4096) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["configs"] if c["name"] == CELL_CONFIG]
    with open(os.path.join(ROOT, entry["file"])) as f:
        c = json.load(f)
    return dict(B=1, T=positions, H=c["linear_num_key_heads"],
                dk=c["linear_key_head_dim"], dv=c["linear_value_head_dim"])


def draw(s, seed: int):
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.key(seed), 6)
    B, T, H, dk, dv = s["B"], s["T"], s["H"], s["dk"], s["dv"]

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = l2(jax.random.normal(keys[0], (B, T, H, dk))) / dk ** 0.5
    k = l2(jax.random.normal(keys[1], (B, T, H, dk)))
    v = jax.nn.silu(jax.random.normal(keys[2], (B, T, H, dv)))
    g = -jax.nn.softplus(jax.random.normal(keys[3], (B, T, H)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], (B, T, H)))
    ct = jax.random.normal(keys[5], (B, T, H, dv))
    return (q, k, v, g, beta), ct


def build(rule):
    import jax
    import jax.numpy as jnp

    def forward(*args):
        return rule(*args, jnp.bfloat16)

    def backward(args, ct):
        return jax.grad(lambda a: jnp.vdot(forward(*a), ct))(args)

    return jax.jit(forward), jax.jit(backward)


def scan_computations(text: str) -> set:
    """Computations run inside a ``while`` loop of a compiled module."""
    calls, bodies, computation = collections.defaultdict(set), set(), None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            computation = m.group("name")
            continue
        for called in _CALLED.findall(line):
            for name in called.split(","):
                calls[computation].add(name.strip().lstrip("%"))
        if " while(" in line:
            body = re.search(r"body=%?([\w.\-]+)", line)
            if body:
                bodies.add(body.group(1))
    seen, todo = set(), list(bodies)
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo.extend(calls.get(c, ()))
    return seen


def op_computations(text: str) -> dict:
    """``{instruction: computation}`` of a compiled module."""
    out, computation = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            computation = m.group("name")
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
        if m:
            out[m.group(1)] = computation
    return out


def device_ms_by_part(trace_dir: str, text: str, calls: int) -> dict:
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    in_scan, where = scan_computations(text), op_computations(text)
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
                if name.split(".")[0] in KERNELS:
                    part = "kernel"
                elif name not in where or name.split(".")[0] in FRAMES:
                    continue  # elsewhere, or a frame around other ops
                elif where[name] in in_scan:
                    part = "scan"
                else:
                    part = "chunk_local"
                ms = ev.duration_ns / 1e6
                totals[part] += ms
                ops[name] += ms
    out = {k: round(v / calls, 4) for k, v in sorted(totals.items())}
    out["top_ops"] = [[name, round(ms / calls, 4)]
                      for name, ms in ops.most_common(10)]
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--root", default=ROOT)
    parser.add_argument("--seed", type=int, default=2147483907)
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--forms", default="xla,auto")
    parser.add_argument("--positions", type=int, default=4096)
    parser.add_argument("--trace", help="profile into this directory")
    parser.add_argument("--variants", default="",
                        help="also time the kernels at these ops/delta_rule "
                        "STACKxHEAD_BLOCK values, e.g. 1x6,2x6")
    args = parser.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.root))
    import jax
    import jax.numpy as jnp

    from distributed_learning_simulator_tpu.models import lm_parts as parts

    s = shapes_of_cell(args.positions)
    inputs, ct = draw(s, args.seed)
    out = {"root": os.path.relpath(os.path.abspath(args.root), ROOT), **s,
           "platform": jax.devices()[0].platform,
           "device_kind": jax.devices()[0].device_kind}
    first = None
    for form in args.forms.split(","):
        rule = parts.chunked_delta_rule if form == "auto" else getattr(
            parts, "chunked_delta_rule_xla", parts.chunked_delta_rule)
        forward, backward = build(rule)
        results = dict(zip(("o", "q", "k", "v", "log_decay", "beta"),
                           (forward(*inputs),) + backward(inputs, ct)))
        row = {"forward_ms": median_ms(forward, inputs, args.iters),
               "forward_backward_ms": median_ms(backward, (inputs, ct),
                                                args.iters),
               "summary": summary(results)}
        if first is None:
            first = results
        else:
            row["relative_to_first"] = {
                name: float(jnp.linalg.norm(a - first[name])
                            / jnp.linalg.norm(first[name]))
                for name, a in results.items()}
        if args.trace:
            calls = 5
            for name, fn, fargs in (("forward", forward, inputs),
                                    ("forward_backward", backward,
                                     (inputs, ct))):
                where = os.path.join(args.trace, form, name)
                text = fn.lower(*fargs).compile().as_text()
                jax.block_until_ready(fn(*fargs))
                with jax.profiler.trace(where):
                    for _ in range(calls):
                        jax.block_until_ready(fn(*fargs))
                row[f"{name}_device_ms_by_part"] = device_ms_by_part(
                    where, text, calls)
        out[form] = row
    if args.variants:
        from distributed_learning_simulator_tpu.ops import delta_rule

        for variant in args.variants.split(","):
            delta_rule.STACK, delta_rule.HEAD_BLOCK = map(
                int, variant.split("x"))
            forward, backward = build(parts.chunked_delta_rule)
            out[f"variant_{variant}"] = {
                "forward_ms": median_ms(forward, inputs, args.iters),
                "forward_backward_ms": median_ms(backward, (inputs, ct),
                                                 args.iters)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
