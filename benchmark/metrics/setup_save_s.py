"""Seconds of the followed rounds' checkpoint before the window opened:
the union of the program's ``save/fetch`` (device -> host) and
``save/write`` (pickle, CRC, write, rename) spans, inside the last
followed round's ``checkpoint``, clipped at the opening (the window
opens once the file is there). ``None`` on a program without the spans
or whose ring has lost spans."""

from harness import hostspans


def read(ctx):
    rec = hostspans.recorder()
    if rec is None or not hostspans.whole(rec):
        return None
    spans = [s for s in rec.spans()
             if s["name"] in ("save/fetch", "save/write")]
    if not spans:
        return None
    return hostspans.union(hostspans.intervals(spans), hi=ctx["opened_at"])
