"""The router's and the held routed experts' share of their roofline over
the forward passes, in the AFMoE cell: the least time the forward passes
of one round could take in that part (the larger of FLOPs over the bf16
peak and bytes over the HBM peak, from shapes: ``flops/afmoe.py``
``moe_forward``, the experts by their expected 1.0 assignment a token) over
``afmoe_moe_ms_per_round``, which times the same passes."""

from harness import scopes


def read(ctx):
    ms = scopes.ms_per_round(ctx, {"moe/route", "moe/experts"})
    if ms is None:
        return None
    work = scopes.load_flops(ctx).moe_forward(
        ctx["spec"]["config"]["model"], *scopes.forward_passes(ctx)
    )
    return scopes.roofline_pct(ctx, ms, work)
