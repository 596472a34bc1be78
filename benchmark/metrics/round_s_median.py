"""Median of the window's per-round seconds (the program's own
``round_seconds``: host clock between successive metric fetches). The
steady statistic that stands beside the all-window rate."""

import statistics


def read(ctx):
    if not ctx["window_seconds"]:
        return None
    return statistics.median(ctx["window_seconds"])
