"""``host_sync`` spans (each the one ``jax.device_get`` of a round's
metrics) that ended in the window, per round completed there. Expected
1: a single device fetch a round."""

from harness import hostspans


def read(ctx):
    rec = hostspans.recorder()
    if rec is None or not hostspans.whole(rec, ctx["opened_at"]):
        return None
    stamps = hostspans.window_rounds(rec, ctx["opened_at"])
    if not stamps:
        return None
    ended_in_window = [
        b for _, b in hostspans.host_syncs(rec)
        if ctx["opened_at"] < b <= stamps[-1]
    ]
    return len(ended_in_window) / len(stamps)
