"""Backend compiles after the window opened (the benchmark's listener),
or the program's own count after its first round if that is larger.
Expected 0: nothing may compile inside the measured window."""


def read(ctx):
    own = sum(1 for t, _ in ctx["compile_events"] if t > ctx["opened_at"])
    return max(own, ctx["post_warmup_compiles"] or 0)
