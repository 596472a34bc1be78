"""Share of the traced periods in which no op ran, on the busiest chip
(the one with the least idle), in percent."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    return min(
        100.0 * (1.0 - c["busy_s"] / c["window_s"]) for c in trace["chips"]
    )
