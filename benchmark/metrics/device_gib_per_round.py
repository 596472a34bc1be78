"""``raw_bytes_accessed`` summed over the device ops of whole traced
periods, per period, over all chips, in GiB. A count (it repeats exactly
for one program; it includes re-reads inside fusions), for comparing two
versions of one program; never divided by a peak."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or any(c["bytes"] is None for c in trace["chips"]):
        return None
    return sum(c["bytes"] / c["periods"] for c in trace["chips"]) / 2**30
