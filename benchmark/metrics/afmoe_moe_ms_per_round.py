"""Device milliseconds a round in routing and in the routed experts held
here, forward passes, in the AFMoE cell (16 of 128 experts held, an
expected 1.0 local assignment a token): the summed durations of the ops
that carry a line of the code of scopes ``moe/route`` and ``moe/experts``
of the model the configuration names (``harness/scopes.py``: the training
steps' first forward pass and the server evaluation's, not the backward
pass), over whole traced periods of the round program, per period. The
shared expert (``moe/shared``) is not in it. ``moe_ms_per_round`` reads
the same scopes in the Solar-Open2 cell; a metric's cells report one
end-to-end regime each, so this cell has its own."""

from harness import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, {"moe/route", "moe/experts"})
