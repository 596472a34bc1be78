"""Device milliseconds a round in the Gated DeltaNet form of the delta
rule's kernels, found by their names (``ops/delta_rule.py``
``FORWARD_NAME``, ``BACKWARD_NAME``; a trace names each launch
``<name>.<n>``): every launch of both, the training steps' first forward
pass, a rematerialised block's recomputed one, the server evaluation's and
the backward pass alike, over whole traced periods of the round program,
per period. Nothing to read in a program without the kernels (the
parent's, a CPU run, a rule with a decay per key channel)."""

#: The kernels' names in a device trace.
KERNELS = ("delta_rule_forward", "delta_rule_backward")


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    rows = [row for row in trace["ops"]["table"]
            if str(row.get("name", "")).split(".")[0] in KERNELS]
    if not rows:
        return None
    return 1e3 * sum(row["seconds"] for row in rows) / trace["ops"][
        "periods"]
