"""Per-round phase accounting: the ``phase_seconds`` of a round's record.

In ``run_simulation`` the clocks are the span recorder's
(telemetry/spans.py): one ``tracer.span(name, ..., phase=<phase>)`` per
boundary reads the clock once per edge and feeds the duration through
:meth:`PhaseTimer.add`. The threaded oracle (execution/threaded.py) has
no recorder and times its phases with :meth:`PhaseTimer.phase`.

A round's wall-clock splits into: ``client_step`` (the fused round
program's dispatch — local training AND the in-program aggregation; XLA
fuses them, so they are one phase by construction), ``aggregate`` (the
server-optimizer post-step, when configured), ``eval``, ``host_sync``
(the deferred device->host metric fetch), ``post_round`` (host-side
algorithm work, e.g. Shapley scoring), and — under streamed residency
with a sampled cohort — ``sample`` (the host-side cohort-draw replay,
``parallel/streaming.CohortStreamer.cohort_for``; carved out of the
``client_step`` window it overlaps via :meth:`PhaseTimer.carve`).

Two fidelity modes, selected by ``config.telemetry_level``:

* ``basic`` — monotonic clocks only. JAX dispatch is asynchronous, so a
  dispatch phase measures trace+dispatch cost while the device time it
  launched pools into whichever later phase first blocks (usually
  ``host_sync``). Zero perturbation of the measured program.
* ``detailed`` — each phase fences on its output
  (``jax.block_until_ready``) before the clock stops, so the split is
  true per-phase device time. Fencing serializes dispatch with
  execution, which defeats round pipelining's transfer/compute overlap —
  a measurement mode, not a production mode.

``telemetry_level='off'`` gets the :class:`NullPhaseTimer`, whose phase
contexts are no-ops — the default program is untouched.
"""

from __future__ import annotations

import contextlib

import jax

from distributed_learning_simulator_tpu.telemetry import clock


class _FenceBox:
    """Mutable slot a phase body parks its output in; a fencing timer
    blocks on it before stopping the clock (``fence`` is a no-op record
    under ``basic`` — the value is simply not waited on)."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = None

    def fence(self, value) -> None:
        self.value = value


class PhaseTimer:
    """Accumulates per-(round, phase) wall-clock seconds.

    Keyed by round index because round pipelining interleaves rounds:
    round r's ``host_sync``/``post_round`` run after round r+1's
    ``client_step`` has been dispatched. ``take(round_idx)`` pops the
    finished round's dict for its metrics record.
    """

    enabled = True

    def __init__(self, fence: bool = False):
        self._fence = fence
        self._acc: dict[int, dict[str, float]] = {}

    @contextlib.contextmanager
    def phase(self, round_idx: int, name: str):
        box = _FenceBox()
        t0 = clock.monotonic()
        try:
            yield box
        finally:
            self.fence(box)
            self.add(round_idx, name, clock.monotonic() - t0)

    def fence(self, box) -> None:
        """Wait for what the phase body parked in ``box`` (``detailed``
        only); called before the clock stops."""
        if self._fence and box.value is not None:
            jax.block_until_ready(box.value)

    def add(self, round_idx: int, name: str, seconds: float) -> None:
        """Accumulate ``seconds`` a caller timed itself: the span
        recorder (telemetry/spans.py) feeds every phase through here
        from its own two clock reads."""
        acc = self._acc.setdefault(round_idx, {})
        acc[name] = acc.get(name, 0.0) + seconds

    def take(self, round_idx: int) -> dict[str, float]:
        """Pop the round's accumulated phase seconds (empty dict if the
        round recorded nothing)."""
        return self._acc.pop(round_idx, {})

    def carve(self, round_idx: int, name: str, seconds: float,
              source: str) -> None:
        """Re-attribute ``seconds`` of host work from the OPEN ``source``
        phase window to its own named phase.

        Used for the streamed cohort-draw replay (``sample``): the draw
        for the next dispatch deliberately runs after the current
        dispatch launches — inside the ``client_step`` region, so it
        overlaps device compute — but its host cost (the ~1 s exact
        replay at N=1e6) must be visible in the phase table, not hidden
        in ``client_step``. The negative accumulation nets out when the
        enclosing context exits and adds its full wall; phases stay
        disjoint.
        """
        acc = self._acc.setdefault(round_idx, {})
        acc[name] = acc.get(name, 0.0) + seconds
        acc[source] = acc.get(source, 0.0) - seconds


class NullPhaseTimer:
    """``telemetry_level='off'``: same API, no clocks, no records."""

    enabled = False

    @contextlib.contextmanager
    def phase(self, round_idx: int, name: str):
        yield _FenceBox()

    def fence(self, box) -> None:
        return None

    def add(self, round_idx: int, name: str, seconds: float) -> None:
        return None

    def take(self, round_idx: int) -> None:
        return None

    def carve(self, round_idx: int, name: str, seconds: float,
              source: str) -> None:
        return None


def make_phase_timer(level: str) -> PhaseTimer | NullPhaseTimer:
    """Level -> timer: 'off' is inert, 'basic' clocks without fencing,
    'detailed' fences each phase on its output."""
    level = level.lower()
    if level == "off":
        return NullPhaseTimer()
    return PhaseTimer(fence=(level == "detailed"))
