"""Seconds spent in backend compiles (or loads from the persistent
cache) before the window opened; the benchmark's own ``jax.monitoring``
listener on ``/jax/core/compile/backend_compile_duration``."""


def read(ctx):
    return sum(d for t, d in ctx["compile_events"] if t <= ctx["opened_at"])
