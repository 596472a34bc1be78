"""Device milliseconds a round in the selective scan's FORWARD kernel,
found by its name (``ops/selective_scan.py`` ``FORWARD_NAME``; a trace
names each launch ``<name>.<n>``): every launch, the training steps' first
forward pass, a rematerialised block's recomputed one and the server
evaluation's alike, over whole traced periods of the round program, per
period. Nothing to read in a program without the kernel (the parent's, a
CPU run)."""

#: The forward kernel's name in a device trace.
KERNEL = "selective_scan_forward"


def launches(ctx):
    """``(seconds, launches)`` of the forward kernel a round, or ``None``."""
    trace = ctx["trace"]
    if trace is None:
        return None
    rows = [row for row in trace["ops"]["table"]
            if str(row.get("name", "")).split(".")[0] == KERNEL]
    if not rows:
        return None
    periods = trace["ops"]["periods"]
    return (sum(row["seconds"] for row in rows) / periods,
            sum(row["executions"] for row in rows) / periods)


def read(ctx):
    found = launches(ctx)
    return None if found is None else 1e3 * found[0]
