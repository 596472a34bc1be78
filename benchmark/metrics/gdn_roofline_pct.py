"""The Gated DeltaNet mixers' share of their roofline over the forward
passes: the least time the forward passes of one round (every client's
local steps and the server's evaluation) could take in the linear layers'
mixers (the larger of FLOPs over the bf16 peak and bytes over the HBM
peak, from shapes: ``flops/olmo_hybrid.py`` ``gdn_forward``, the delta
rule by the causal half of its chunk form) over ``gdn_ms_per_round``, which
times the same passes (``harness/scopes.py`` says why the backward pass is
in neither)."""

from harness import scopes


def read(ctx):
    ms = scopes.ms_per_round(ctx, {"gdn"})
    if ms is None:
        return None
    work = scopes.load_flops(ctx).gdn_forward(
        ctx["spec"]["config"]["model"], *scopes.forward_passes(ctx)
    )
    return scopes.roofline_pct(ctx, ms, work)
