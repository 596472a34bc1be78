"""The whole round's share of the chips' peak, from the device trace:
FLOPs the forward and backward passes of one round need (from the
configuration's shapes, ``flops/``; the server's evaluation and anything
recomputed are not counted) over the traced length of a round (start of
one round program to the start of the next, idle and evaluation included)
and the chips' bf16 peak (``peaks.json``). Nothing to read without a
device trace."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    round_s = max(c["window_s"] / c["periods"] for c in trace["chips"])
    return 100.0 * ctx["train_flops_per_round"] / round_s / (
        ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    )
