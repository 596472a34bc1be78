"""What no span covers: ``setup_s`` less the time before the program
(``setup_before_program_s``) less the union of all top-level children of
the root span ``run`` up to the window's opening (clipped there)."""

from harness import hostspans


def read(ctx):
    rec = hostspans.recorder()
    run = hostspans.root(rec) if rec is not None else None
    if run is None or not hostspans.whole(rec):
        return None
    covered = hostspans.union(
        hostspans.intervals(hostspans.top_level(rec)),
        lo=run["t0"], hi=ctx["opened_at"],
    )
    return ctx["opened_at"] - run["t0"] - covered
