"""Device milliseconds a round in the gated delta-rule mixers' forward
passes: the summed durations of the ops that carry a line of the code of
scope ``kda`` (``harness/scopes.py``: the training steps' first forward
pass and the server evaluation's; the rematerialised forward and the
backward pass carry the line of the block's ``nn.remat`` call and are not
in it), over whole traced periods of the round program, per period."""

from harness import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, {"kda"})
