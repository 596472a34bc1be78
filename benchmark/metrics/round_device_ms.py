"""Device-busy milliseconds per round: union of device-op intervals over
whole traced periods of the round program, per period, on the busiest
chip."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    return max(1e3 * c["busy_s"] / c["periods"] for c in trace["chips"])
