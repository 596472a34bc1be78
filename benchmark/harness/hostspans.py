"""What the set-up and host-loop metric readers share: reading the
program's own span recorder.

Since PR 25 ``run_simulation`` keeps one span recorder from its entry to
its return (``distributed_learning_simulator_tpu/telemetry/spans.py``);
``spans.last_run()`` is the recorder of the call the benchmark just made.
Its clock is ``time.perf_counter``, the clock of ``ctx["opened_at"]`` and
of ``T_PROCESS`` (``opened_at - setup_s``), so a reader cuts spans at the
window's opening with no alignment. A program without a recorder (the
parent of the PR that brought it) gives ``None`` and every reader here
then returns ``None``: its metric is left out of the line.

The cutting at the window's opening is the benchmark's and lives here;
the recorder hands over what it recorded: ``spans()`` (dicts with
``name``, ``t0``, ``dur``, ``id``, ``parent``, ``round``, ``thread``),
``round_stamps()`` (``(round, t)`` of each completed round),
``duration_events()`` (``(counter, t_end, seconds)`` of jax's tracing,
lowering and backend-compile events), ``main_thread`` and
``evicted_until``; the union of intervals is the program's own helper
(``spans.union_seconds``).
"""

from __future__ import annotations

INF = float("inf")


def recorder():
    try:
        from distributed_learning_simulator_tpu.telemetry import spans
    except ImportError:
        return None
    last_run = getattr(spans, "last_run", None)
    return last_run() if last_run is not None else None


def union(intervals, lo: float = -INF, hi: float = INF) -> float:
    """Length of the union of ``(start, end)`` intervals within
    ``[lo, hi]``: overlapping and nested ones count once. A program
    that has a recorder has the helper."""
    from distributed_learning_simulator_tpu.telemetry.spans import (
        union_seconds,
    )

    return union_seconds(intervals, lo, hi)


def whole(rec, since: float = -INF) -> bool:
    """Whether the recorder still holds every span that ended after
    ``since``. Set-up sections are kept for good; everything later sits
    in a bounded ring (``--span_buffer_size``, 4096 spans by default:
    about 370 rounds). A reader that needs spans the ring has evicted
    returns ``None``, never a number computed over the holes."""
    until = getattr(rec, "evicted_until", None)
    return until is None or until <= since


def intervals(spans) -> list[tuple[float, float]]:
    return [(s["t0"], s["t0"] + s["dur"]) for s in spans]


def root(rec):
    """The root span ``run`` (entry of ``run_simulation`` to its
    return), or ``None``."""
    for s in rec.spans():
        if s["name"] == "run" and s.get("parent") is None:
            return s
    return None


def top_level(rec) -> list[dict]:
    """The children of ``run``: the set-up sections, one ``round`` per
    loop iteration, and what follows the loop."""
    run = root(rec)
    if run is None:
        return []
    return [s for s in rec.spans() if s.get("parent") == run["id"]]


def seconds_before(rec, name: str, opened_at: float):
    """Seconds the top-level spans called ``name`` cover before the
    window opened (clipped there); ``None`` without a recorder."""
    if rec is None or root(rec) is None:
        return None
    return union(
        intervals(s for s in top_level(rec) if s["name"] == name),
        hi=opened_at,
    )


def window_rounds(rec, opened_at: float) -> list[float]:
    """Stamps of the rounds completed after the window opened: the
    program's own ``now`` that ends each ``round_seconds``."""
    return [t for _, t in rec.round_stamps() if t > opened_at]


def host_syncs(rec) -> list[tuple[float, float]]:
    """The main thread's ``host_sync`` spans: each the one
    ``jax.device_get`` of a round's metrics."""
    return intervals(
        s for s in rec.spans()
        if s["name"] == "host_sync" and s["thread"] == rec.main_thread
    )
