"""What a round costs the host when it is not waiting for the device:
over the window (its opening to the last completed round's stamp),
elapsed time less the union of the main thread's ``host_sync`` spans,
per round completed, in ms. Robust to pipelining: round r's fetch runs
inside round r+1's iteration, and only the fetch is waiting."""

from harness import hostspans


def read(ctx):
    rec = hostspans.recorder()
    if rec is None or not hostspans.whole(rec, ctx["opened_at"]):
        return None
    stamps = hostspans.window_rounds(rec, ctx["opened_at"])
    if not stamps:
        return None
    waiting = hostspans.union(
        hostspans.host_syncs(rec), lo=ctx["opened_at"], hi=stamps[-1]
    )
    elapsed = stamps[-1] - ctx["opened_at"]
    return 1e3 * (elapsed - waiting) / len(stamps)
