"""XLA recompilation counter.

A shape-stable simulation compiles each of its programs exactly once, in
the first executed round (warmup: the round program, the eval program,
and assorted small host jits). Any backend compile AFTER warmup means an
operand's shape/dtype/static-arg changed across rounds — a
shape-instability bug that silently multiplies round cost (the round
program's compile is tens of seconds at flagship scale) — so the round
loop logs it as a WARNING with the offending function name.

Two hooks, combined:

* **Count** — a ``jax.monitoring`` duration listener on the
  ``/jax/core/compile/backend_compile_duration`` event: fires once per
  program LOWERED to the backend, including persistent-cache hits
  (verified on the pinned jax: the event wraps compile_or_get_cached
  unconditionally). That is the right instability signal — a cache hit
  still means a NEW program shape was traced this round — but it means
  the per-event duration, not the count alone, says whether the full
  compile cost was paid.
* **Names** — the monitoring event carries no function name in this JAX
  version, so the monitor additionally flips ``jax_log_compiles`` on and
  captures the ``"Finished XLA compilation of jit(<name>) …"`` lines
  from the ``jax._src.dispatch`` logger. While the monitor is active,
  propagation on the two chatty compile loggers is suspended so the
  capture doesn't spam stderr; both the flag and propagation are
  restored on ``stop()``.

One monitor active per process at a time (it owns process-global logging
state); the simulator scopes it to the round loop.

The monitor's is the program's ONE ``jax.monitoring`` listener. A span
recorder (telemetry/spans.py) makes the run's monitor with an
``on_event`` callback and calls :meth:`RecompileMonitor.listen` at the
first line of ``run_simulation``: from then on every tracing, lowering
and backend-compile duration event is handed to it stamped
(:data:`DURATION_EVENTS`), so the op-by-op model init is seen. Counting
and names still begin at :meth:`RecompileMonitor.start`, at the round
loop, so the warm-up rule does not move.
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
_COMPILE_LOGGER = "jax._src.dispatch"
# "Compiling <fn> with global shapes…" (pxla) and "Persistent compilation
# cache hit…" (compiler) log at the same forced-WARNING level; suspend
# their propagation too while jax_log_compiles is on.
_CHATTY_LOGGERS = (
    _COMPILE_LOGGER,
    "jax._src.interpreters.pxla",
    "jax._src.compiler",
)
_FINISHED_RE = re.compile(
    r"Finished XLA compilation of (?:jit\()?([^)\s]+)\)? in ([0-9.eE+-]+) sec"
)


class _CaptureHandler(logging.Handler):
    def __init__(self, monitor: "RecompileMonitor"):
        super().__init__(level=logging.DEBUG)
        self._monitor = monitor

    def emit(self, record: logging.LogRecord) -> None:
        try:
            m = _FINISHED_RE.search(record.getMessage())
        except Exception:  # pragma: no cover - malformed record
            return
        if m:
            self._monitor._record_name(m.group(1), float(m.group(2)))


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
        # ``on_event(counter, t_end, seconds)``: every DURATION_EVENTS
        # event from listen() on, counted or not (the span recorder's).
        self._on_event = on_event
        self._listening = False
        self._lock = threading.Lock()
        self._count = 0          # monitoring-event ground truth
        self._named: list[tuple[str, float]] = []
        self._per_round: dict[int, list[tuple[str, float]]] = {}
        self._active = False
        self._handler: _CaptureHandler | None = None
        self._saved_log_compiles = False
        self._saved_propagate: dict[str, bool] = {}
        self._null_handlers: dict[str, logging.Handler] = {}

    # -- listener callbacks ---------------------------------------------------
    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        counter = DURATION_EVENTS.get(event)
        if counter is None:
            return
        if self._on_event is not None:
            self._on_event(counter, clock.monotonic(), float(duration))
        if self._active and event == _COMPILE_EVENT:
            with self._lock:
                self._count += 1

    def _record_name(self, name: str, seconds: float) -> None:
        if not self._active:
            return
        with self._lock:
            self._named.append((name, seconds))

    # -- lifecycle ------------------------------------------------------------
    def listen(self) -> None:
        """Register the ``jax.monitoring`` listener (idempotent) without
        counting yet: ``on_event`` sees the events from here on."""
        if not self._listening:
            self._listening = True
            jax.monitoring.register_event_duration_secs_listener(
                self._on_duration
            )

    def start(self) -> "RecompileMonitor":
        if self._active:
            return self
        self._active = True
        self.listen()
        self._handler = _CaptureHandler(self)
        logging.getLogger(_COMPILE_LOGGER).addHandler(self._handler)
        self._null_handlers = {}
        for name in _CHATTY_LOGGERS:
            lg = logging.getLogger(name)
            self._saved_propagate[name] = lg.propagate
            lg.propagate = False
            # propagate=False alone is not silence: a record that finds NO
            # handler anywhere falls through to logging.lastResort (which
            # prints WARNINGs to stderr) — park a NullHandler so the
            # forced-on compile chatter has a sink.
            nh = logging.NullHandler()
            self._null_handlers[name] = nh
            lg.addHandler(nh)
        self._saved_log_compiles = bool(jax.config.jax_log_compiles)
        jax.config.update("jax_log_compiles", True)
        return self

    def stop(self) -> None:
        if self._listening:
            self._listening = False
            jax.monitoring.unregister_event_duration_listener(
                self._on_duration
            )
        if not self._active:
            return
        self._active = False
        jax.config.update("jax_log_compiles", self._saved_log_compiles)
        for name, prop in self._saved_propagate.items():
            logging.getLogger(name).propagate = prop
        self._saved_propagate.clear()
        for name, nh in getattr(self, "_null_handlers", {}).items():
            logging.getLogger(name).removeHandler(nh)
        self._null_handlers = {}
        if self._handler is not None:
            logging.getLogger(_COMPILE_LOGGER).removeHandler(self._handler)
            self._handler = None

    def __enter__(self) -> "RecompileMonitor":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- draining -------------------------------------------------------------
    def drain(self) -> list[tuple[str, float]]:
        """Pop the events recorded since the last drain as
        ``[(fn_name, compile_seconds), ...]``. The monitoring count is the
        ground truth; if a JAX upgrade changes the log format and names go
        missing, the shortfall is padded with ``"<unknown>"`` entries so
        the COUNT is never under-reported."""
        with self._lock:
            named, self._named = self._named, []
            count, self._count = self._count, 0
        while len(named) < count:
            named.append(("<unknown>", 0.0))
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
