"""Seconds of jax tracing and lowering before the window opened, of the
round program alone: the program whose name is the device trace's
``round_module`` (``jit_round_fn``), in the program's own ledger of
programs (the recorder's ``programs()``; names made one by the
program's ``telemetry.recompile.program_name``). A union of the
intervals, so the programs traced inside the round program's trace are
in it. An "of which" of ``setup_trace_lower_s``. ``None`` on a program
without the ledger, without a reduced trace, or whose ring has lost
events."""

from harness import hostspans


def read(ctx):
    rec = hostspans.recorder()
    programs = getattr(rec, "programs", None)
    if programs is None or not ctx["trace"] or not hostspans.whole(rec):
        return None
    from distributed_learning_simulator_tpu.telemetry.recompile import (
        program_name,
    )

    entry = programs().get(program_name(ctx["trace"]["round_module"]))
    if entry is None:
        return None
    return hostspans.union(
        [iv for key in ("trace_s", "lower_s") for half in entry[key]
         for iv in half],
        hi=ctx["opened_at"],
    )
