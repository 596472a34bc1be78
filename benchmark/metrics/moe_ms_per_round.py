"""Device milliseconds a round in routing and in the routed experts held
here, forward passes: the summed durations of the ops that carry a line
of the code of scopes ``moe/route`` and ``moe/experts``
(``harness/scopes.py``: the training steps' first forward pass and the
server evaluation's, not the backward pass), over whole traced periods of
the round program, per period. The shared expert (``moe/shared``) is not
in it."""

from harness import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, {"moe/route", "moe/experts"})
