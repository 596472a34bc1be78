"""The banded attention's share of its roofline over the forward passes:
the least time the forward passes of one round (every client's local steps
and the server's evaluation) could take in the sliding layers' attention
(the larger of FLOPs over the bf16 peak and bytes over the HBM peak, from
shapes: ``flops/afmoe.py`` ``swa_forward``, each query over the keys its
window lets it see) over ``swa_ms_per_round``, which times the same passes
(``harness/scopes.py`` says why the backward pass is in neither)."""

from harness import scopes


def read(ctx):
    ms = scopes.ms_per_round(ctx, {"swa"})
    if ms is None:
        return None
    config = ctx["spec"]["config"]
    work = scopes.load_flops(ctx).swa_forward(
        config["model"], *scopes.forward_passes(ctx),
        length=config["data"]["shape"][0],
    )
    return scopes.roofline_pct(ctx, ms, work)
