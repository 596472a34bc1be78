"""Seconds of backend compiles before the window opened that XLA
compiled and the persistent cache then stored (outcome ``miss`` in the
program's ledger of programs, the recorder's ``programs()``): none on a
run whose programs the cache held, most of ``compile_s`` on a run that
started with an empty one. ``None`` on a program without the ledger or
whose ring has lost events."""

from harness import hostspans


def read(ctx):
    rec = hostspans.recorder()
    programs = getattr(rec, "programs", None)
    if programs is None or not hostspans.whole(rec):
        return None
    return hostspans.union(
        [iv for entry in programs().values()
         for half in entry["cache"]["miss"] for iv in half],
        hi=ctx["opened_at"],
    )
