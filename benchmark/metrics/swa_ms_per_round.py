"""Device milliseconds a round in the banded sliding-window attention's
forward passes (projections to output): the summed durations of the ops
that carry a line of the code of scope ``swa`` (``harness/scopes.py``: the
training steps' first forward pass and the server evaluation's; the
rematerialised forward and the backward pass carry the line of the
block's ``nn.remat`` call and are not in it), over whole traced periods of
the round program, per period. Nothing to read in a program without that
scope."""

from harness import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, {"swa"})
