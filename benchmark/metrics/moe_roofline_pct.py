"""The router's and the held routed experts' share of their roofline over the forward passes:
the least time the forward passes of one round (every client's local steps
and the server's evaluation) could take in that part (the larger of FLOPs
over the bf16 peak and bytes over the HBM peak, from shapes:
``flops/solar_open2.py`` ``moe_forward``) over ``moe_ms_per_round``, which
times the same passes (``harness/scopes.py`` says why the backward pass is
in neither)."""

from harness import scopes


def read(ctx):
    ms = scopes.ms_per_round(ctx, {"moe/route", "moe/experts"})
    if ms is None:
        return None
    work = scopes.load_flops(ctx).moe_forward(
        ctx["spec"]["config"]["model"], *scopes.forward_passes(ctx)
    )
    return scopes.roofline_pct(ctx, ms, work)
