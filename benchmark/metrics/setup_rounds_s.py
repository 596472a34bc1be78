"""Set-up spent in the round loop: the union of the program's ``round``
spans (one per loop iteration) before the window opened, clipped at the
opening. The followed rounds; the first carries tracing, lowering and
the compile or cache load. With pipelining the iteration that fetches
and checkpoints the last followed round straddles the opening."""

from harness import hostspans


def read(ctx):
    rec = hostspans.recorder()
    if rec is None or not hostspans.whole(rec):
        return None  # the ring's oldest spans are these rounds'
    return hostspans.seconds_before(rec, "round", ctx["opened_at"])
