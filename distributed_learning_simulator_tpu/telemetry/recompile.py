"""XLA recompilation counter, and the ledger of the run's programs.

A shape-stable simulation compiles each of its programs exactly once, in
the first executed round (warmup: the round program, the eval program,
and assorted small host jits). Any backend compile AFTER warmup means an
operand's shape/dtype/static-arg changed across rounds — a
shape-instability bug that silently multiplies round cost (the round
program's compile is tens of seconds at flagship scale) — so the round
loop logs it as a WARNING with the offending function name.

Everything comes from jax's own events (jax 0.9.0,
``jax._src.dispatch.log_elapsed_time``), through two ``jax.monitoring``
listeners of one monitor object:

* **Durations** — ``jaxpr_trace_duration``, ``jaxpr_to_mlir_module_duration``
  and ``backend_compile_duration`` (:data:`DURATION_EVENTS`), each with
  the ``fun_name`` of its program: ``round_fn`` for a trace,
  ``jit(round_fn)`` for a lowering and a compile. :func:`program_name`
  makes those one name. The backend event fires once per program
  LOWERED to the backend, whether XLA compiled it or it was loaded from
  the persistent cache: a new program shape either way, the right
  instability signal.
* **The cache's outcome** — the plain events
  ``/jax/compilation_cache/cache_hits`` (an executable loaded) and
  ``/jax/compilation_cache/cache_misses`` (an executable compiled and
  written as a new entry) fire inside the backend-compile event, on the
  same thread (:data:`CACHE_EVENTS`). The next backend event on that
  thread carries the mark; with neither signal it is ``"uncached"`` (no
  cache, or an entry below the cache's thresholds).

The monitor touches no logger and no ``jax_log_compiles``.

The monitor's listeners are the program's ONE pair. A span recorder
(telemetry/spans.py) makes the run's monitor with an ``on_event``
callback and calls :meth:`RecompileMonitor.listen` at the first line of
``run_simulation``: from then on every tracing, lowering and
backend-compile event is handed to it stamped, named and (compiles)
marked, so the op-by-op model init is seen. Counting for the records
still begins at :meth:`RecompileMonitor.start`, at the round loop, so
the warm-up rule does not move.
"""

from __future__ import annotations

import logging
import re
import threading

import jax

from distributed_learning_simulator_tpu.telemetry import clock

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: ``jax.monitoring`` duration events -> the counter each feeds (jax
#: 0.9.0: ``JAXPR_TRACE_EVENT``, ``JAXPR_TO_MLIR_MODULE_EVENT`` and
#: ``BACKEND_COMPILE_EVENT`` of ``jax._src.dispatch``). The backend event
#: wraps compile-or-load-from-the-persistent-cache.
DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    _COMPILE_EVENT: "compile_s",
}
#: The persistent cache's plain events -> the outcome they mark on the
#: backend compile open on the same thread (jax 0.9.0:
#: ``compiler.compile_or_get_cached`` signals a hit,
#: ``compilation_cache.put_executable_and_time`` an entry written).
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_JIT_WRAPPED = re.compile(r"jit\((.*)\)")


def program_name(name: str) -> str:
    """The one name of a program across jax's events and the device
    trace: ``jit(round_fn)`` (lowering and compile events),
    ``jit_round_fn`` (the HLO module a profile names) and ``round_fn``
    (the trace event) are all ``round_fn``."""
    m = _JIT_WRAPPED.fullmatch(name)
    if m:
        name = m.group(1)
    while name.startswith("jit_"):
        name = name[len("jit_"):]
    return name


class RecompileMonitor:
    """Counts XLA backend compiles and attributes them to rounds.

    Usage (the simulator's round loop)::

        with RecompileMonitor() as mon:
            for round_idx in ...:
                dispatch(...)
                mon.attribute(round_idx)   # drain events -> this round
            ...
            events = mon.take(round_idx)   # [(fn_name, seconds), ...]
    """

    def __init__(self, on_event=None):
        # ``on_event(counter, t_end, seconds, program, cache)``: every
        # DURATION_EVENTS event from listen() on, counted or not (the
        # span recorder's); ``cache`` is the compile's outcome, ``None``
        # on a trace or a lowering.
        self._on_event = on_event
        self._listening = False
        self._lock = threading.Lock()
        # The cache outcome signalled inside the backend compile open on
        # each thread, until that compile's duration event takes it.
        self._marks = threading.local()
        self._named: list[tuple[str, float]] = []
        self._per_round: dict[int, list[tuple[str, float]]] = {}
        self._active = False

    # -- listener callbacks ---------------------------------------------------
    def _on_cache(self, event: str, **kwargs) -> None:
        outcome = CACHE_EVENTS.get(event)
        if outcome is not None:
            self._marks.cache = outcome

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        counter = DURATION_EVENTS.get(event)
        if counter is None:
            return
        program = program_name(str(kwargs.get("fun_name", "")))
        cache = None
        if event == _COMPILE_EVENT:
            cache = getattr(self._marks, "cache", None) or "uncached"
            self._marks.cache = None
            if self._active:
                with self._lock:
                    self._named.append((program, float(duration)))
        if self._on_event is not None:
            self._on_event(counter, clock.monotonic(), float(duration),
                           program, cache)

    # -- lifecycle ------------------------------------------------------------
    def listen(self) -> None:
        """Register the ``jax.monitoring`` listeners (idempotent) without
        counting yet: ``on_event`` sees the events from here on."""
        if not self._listening:
            self._listening = True
            jax.monitoring.register_event_listener(self._on_cache)
            jax.monitoring.register_event_duration_secs_listener(
                self._on_duration
            )

    def start(self) -> "RecompileMonitor":
        """Count from here on (and listen, if not yet)."""
        self._active = True
        self.listen()
        return self

    def stop(self) -> None:
        """Stop counting and unregister the listeners (idempotent); what
        was counted stays drainable."""
        self._active = False
        if self._listening:
            self._listening = False
            jax.monitoring.unregister_event_duration_listener(
                self._on_duration
            )
            jax.monitoring.unregister_event_listener(self._on_cache)

    def __enter__(self) -> "RecompileMonitor":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- draining -------------------------------------------------------------
    def drain(self) -> list[tuple[str, float]]:
        """Pop the backend compiles counted since the last drain as
        ``[(program, compile_seconds), ...]``, one per event."""
        with self._lock:
            named, self._named = self._named, []
        return named

    def attribute(self, round_idx: int) -> None:
        """Drain pending events into ``round_idx``'s bucket. Called right
        after each dispatch site (compiles are synchronous with trace/
        lower, so events pending here belong to the calls just made)."""
        events = self.drain()
        if events:
            self._per_round.setdefault(round_idx, []).extend(events)

    def take(self, round_idx: int) -> list[tuple[str, float]]:
        """Pop the events attributed to ``round_idx``."""
        return self._per_round.pop(round_idx, [])


def log_round_compiles(
    logger: logging.Logger,
    round_idx: int,
    events: list[tuple[str, float]],
    warmup: bool,
) -> int:
    """Log a round's compile events; returns the count.

    Warmup compiles (the first executed round) are expected and logged at
    INFO. Post-warmup compiles are the shape-instability signal — logged
    as a WARNING naming the offending function(s) so the bug is
    attributable without a profiler. (The memoized Shapley subset
    evaluator legitimately compiles new wave shapes in later rounds —
    docs/OBSERVABILITY.md covers reading the names.)
    """
    if not events:
        return 0
    names = ", ".join(f"{name} ({secs:.1f}s)" for name, secs in events)
    if warmup:
        logger.info(
            "round %d: %d XLA compile(s) during warmup: %s",
            round_idx, len(events), names,
        )
    else:
        logger.warning(
            "round %d: %d XLA recompile(s) AFTER warmup — shape-unstable "
            "round program? offending: %s",
            round_idx, len(events), names,
        )
    return len(events)
