"""Fullest chip's ``peak_bytes_in_use + peak_bytes_reserved`` after the
window, in GiB. On this backend the first is the high-water mark of live
arrays only and the round program's temporaries sit in the second
(PERF.md, PR 21), so the sum is what the run needed; read before the
reference touches the chip."""


def read(ctx):
    if not ctx["memory"]:
        return None
    peak = max(m["in_use"] + m["reserved"] for m in ctx["memory"])
    return peak / 2**30 if peak else None
