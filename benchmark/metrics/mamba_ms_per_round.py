"""Device milliseconds a round in the Mamba mixers (projections to
``W_out``, the convolution and the selective scan): the summed durations
of the ops that carry a line of the code of scopes ``mamba`` and
``ssm_scan`` (``harness/scopes.py``: the training steps' first forward
pass and the server evaluation's; a rematerialised block's recomputed
forward and backward carry the line of its ``nn.remat`` call and are not
in it, while a block that keeps its residuals has its backward in it too),
over whole traced periods of the round program, per period. Nothing to
read in a program without those scopes."""

from harness import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, {"mamba", "ssm_scan"})
