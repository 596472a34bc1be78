"""Seconds of the program's ``resume/place`` spans before the window:
the restored host trees (global model, client and server state) placed
on the device, inside ``setup/resume``. Set-up spans, which the
recorder never evicts. ``None`` on a program without the span."""

from harness import hostspans


def read(ctx):
    rec = hostspans.recorder()
    if rec is None:
        return None
    spans = [s for s in rec.spans() if s["name"] == "resume/place"]
    if not spans:
        return None
    return hostspans.union(hostspans.intervals(spans), hi=ctx["opened_at"])
