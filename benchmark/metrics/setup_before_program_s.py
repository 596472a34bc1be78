"""Process start to the entry of ``run_simulation``: imports, the
benchmark's data, weights and starting checkpoint. The root span ``run``
of the program's recorder begins at that entry; process start is
``opened_at - setup_s`` on the same clock (``time.perf_counter``)."""

from harness import hostspans


def read(ctx):
    rec = hostspans.recorder()
    run = hostspans.root(rec) if rec is not None else None
    if run is None:
        return None
    return run["t0"] - (ctx["opened_at"] - ctx["setup_s"])
