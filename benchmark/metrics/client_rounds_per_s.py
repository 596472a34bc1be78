"""Clients trained a round x rounds completed in the window / the sum of
those rounds' seconds (the program's host clock between successive metric
fetches). All rounds over all their time: a stall lowers it."""


def read(ctx):
    seconds = ctx["window_seconds"]
    if not seconds:
        return None
    return ctx["clients"] * len(seconds) / sum(seconds)
