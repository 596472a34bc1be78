"""What the readers of a model's named scopes share: device time by scope.

A device trace names each op by the source line it was traced from
(``trace["ops"]["table"][i]["source"]``, ``<file>:<line>``; the scope
path itself is not among the labels ``harness/trace.py`` keeps). The
model file says which of its lines belong to which scope (the
configuration's file names the module and its lookup under ``"scopes"``;
``models/solar_open2.py`` ``scope_of_line``: each scope's code is a
function of its own, wrapped in the ``jax.named_scope`` of that name), so
an op's time goes to the scope whose code produced it. On the chip that
holds for the training step's first forward pass and for the server
evaluation's; the rematerialised forward and the backward pass of a
block carry the line of its ``nn.remat`` call (542 of 1267 ms a round, my
chip run, PR 29) and go to no scope, like the ops traced from elsewhere
(the optimizer's update, the stochastic rounding, the aggregate, norms). A program without the model file, a run without a
device trace, or a trace whose ops carry no ``source`` gives ``None``.
"""

from __future__ import annotations

import os

def scope_lookup(ctx):
    """``(file, lookup)`` of the model whose scopes the configuration's
    file names under ``"scopes"`` (``{"module": ..., "lookup": ...}``:
    the program's module and its function from a line to a scope's name),
    or ``None`` where the program has no such module."""
    import importlib

    named = ctx["spec"]["config"].get("scopes")
    if not named:
        return None
    try:
        module = importlib.import_module(named["module"])
    except ImportError:
        return None
    lookup = getattr(module, named["lookup"], None)
    return None if lookup is None else (module.__file__, lookup)


def ms_per_round(ctx, scopes) -> float | None:
    """Summed device milliseconds a round of the ops whose source line
    lies in one of ``scopes``, on the busiest chip."""
    trace = ctx["trace"]
    found = scope_lookup(ctx) if trace is not None else None
    if found is None:
        return None
    model_file, lookup = found
    total, seen = 0.0, False
    for row in trace["ops"]["table"]:
        path, _, line = str(row.get("source", "")).rpartition(":")
        if not line.isdigit() or (
                os.path.basename(path) != os.path.basename(model_file)):
            continue
        if lookup(int(line)) in scopes:
            total += row["seconds"]
            seen = True
    if not seen:
        return None
    return 1e3 * total / trace["ops"]["periods"]


def forward_passes(ctx) -> tuple[int, int]:
    """``(tokens, passes)`` of the forward passes a round makes whose ops
    carry a scope's line: every client's local steps and the server's
    evaluation, from the cell's argv and data."""
    config = ctx["spec"]["config"]
    argv = list(config["argv"]) + list(ctx["spec"]["traffic"]["argv"])

    def arg(name):
        return int(argv[argv.index(name) + 1])

    data = config["data"]
    shard = data["n_train"] // ctx["clients"]
    steps = ctx["clients"] * arg("--epoch") * (shard // arg("--batch_size"))
    eval_passes = -(-data["n_test"] // arg("--eval_batch_size"))
    tokens_a_sample = ctx["work_per_round"] // (
        ctx["clients"] * shard * arg("--epoch"))
    return (ctx["work_per_round"] + data["n_test"] * tokens_a_sample,
            steps + eval_passes)


def roofline_pct(ctx, ms, flops_and_bytes) -> float | None:
    """The least time the chip could take for ``(FLOPs, bytes)`` a round
    (the larger of operations over the bf16 peak and bytes over the HBM
    peak) over the ``ms`` it took, in percent."""
    if ms is None or ms <= 0:
        return None
    flops, bytes_ = flops_and_bytes
    peaks = ctx["peaks"]
    least_s = max(flops / peaks["bf16_flops_per_s"],
                  bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)


def load_flops(ctx):
    """The configuration's FLOP-count module (``flops/<name>.py``)."""
    import importlib.util

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "flops", ctx["spec"]["config"]["flops"] + ".py",
    )
    spec = importlib.util.spec_from_file_location("bench_flops_scopes", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
