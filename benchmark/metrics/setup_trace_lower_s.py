"""Seconds of jax tracing (``jaxpr_trace_duration``) and lowering
(``jaxpr_to_mlir_module_duration``) before the window opened, from the
program recorder's ``jax.monitoring`` listener, which is on from the
first line of ``run_simulation``. A union of the events' intervals:
traces nest in traces and in lowerings. An "of which": it overlaps the
set-up spans like ``compile_s`` does."""

from harness import hostspans


def read(ctx):
    rec = hostspans.recorder()
    if rec is None:
        return None
    return hostspans.union(
        [(t - d, t) for key, t, d in rec.duration_events()
         if key in ("trace_s", "lower_s")],
        hi=ctx["opened_at"],
    )
