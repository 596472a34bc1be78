"""The one span recorder of a run: host spans, counters, journals.

``run_simulation`` makes one :class:`SpanRecorder` at its first line
whenever ``telemetry_level != 'off'`` or ``span_trace == 'on'`` and keeps
it to its return (:func:`start_run`, then ``start()`` ... ``finish()``
around the root span ``run``; the most recent one stays readable through
:func:`last_run`). Every timed boundary of the program is ONE
call, ``tracer.span(name, cat, round_idx=..., phase=...)``, which

1. enters a ``jax.profiler.TraceAnnotation`` of the same name (metadata
   ``cat`` and ``round``), so a profiler capture holds the host spans on
   the device trace's own clock (``utils/tracing.attribute_idle_gaps``);
2. records the span: ``name``, ``cat``, ``t0``/``dur``
   (``clock.monotonic()``, i.e. ``time.perf_counter``), ``id``,
   ``parent`` (the innermost span open on the same thread when it began),
   ``round`` (the request identifier: every span of one federated round
   carries the same one, also when pipelining runs round r's fetch inside
   round r+1's iteration) and ``thread``;
3. feeds the per-round phase accumulation ``phase_seconds`` is built
   from (``telemetry/phases.PhaseTimer.add``), from the same two clock
   reads, and fences first under ``telemetry_level='detailed'``.

Spans that end before the first round completes (set-up) are kept in a
list of their own that is never evicted; later ones in a bounded ring
(``deque(maxlen=capacity)``; an entry evicted before it reached a
journal counts into ``dropped``; ``evicted_until`` says up to when the
ring has lost anything, so a reader knows whether its window is whole).
Counters sit at the same boundaries: completed rounds with their stamps
(:meth:`SpanRecorder.round_done`), completed ``host_sync`` spans, counts
fixed when the programs are built (:meth:`SpanRecorder.set_counter`), and
the ``jax.monitoring`` durations of tracing, lowering and backend
compiles (or cache loads), heard from the first line of the run by the
run's ONE listener, the :class:`RecompileMonitor`'s (``trace_s``,
``lower_s``, ``compile_s``; :meth:`SpanRecorder.counters`).

At ``telemetry_level='off'`` with ``span_trace='off'`` there is no
recorder: :class:`NullTracer` enters the profiler annotation and nothing
else (no clock, no record).

``span_trace='on'`` adds, at any level, the cross-host part (PR 16):

* a per-host ``spans_<host_id>.jsonl`` journal, drained by ``flush()``
  once per round;
* the flight recorder. Spans marked ``eager=True`` (the per-round
  ``finalize`` envelope, DCN barrier waits, checkpoint barriers —
  anything that can deadlock or die mid-span) write an ``open`` journal
  line at BEGIN, flushed to the OS before the span body runs: a
  SIGKILL'd process leaves its open-line on disk, so the postmortem
  names the span it died inside without any cleanup code running.
  ``flush_inflight(reason)`` is the soft-failure path (SIGTERM,
  fault-quorum rejection, unhandled crash): last-K completed spans + a
  ``flight`` marker + one ``inflight`` line per still-open span;
* the multihost seams (spill exchange, prefetch occupancy, checkpoint
  shards) and the schema-v12 ``spans`` record sub-object.

Journal line taxonomy (all JSONL, one object per line):

``header``   host identity + clock anchors (``epoch_wall``/``epoch_mono``
             sampled back-to-back) + ``clock_offset_s`` /
             ``clock_uncertainty_s`` vs host 0 — everything
             ``scripts/trace_timeline.py`` needs to stitch journals.
``open``     eager begin marker (flight recorder); matched by a later
             ``span`` line with the same ``id`` unless the host died.
``span``     completed span: ``t0`` (monotonic), ``dur`` seconds.
``event``    instant event (recompiles; cat ``counter``: a build-time
             count, ``attrs.value``).
``flight``   force-flush marker with the triggering ``reason`` and, when
             an exception unwound through a span first, the ``in_span``
             it escaped from (name/cat/round + exception type).
``inflight`` a span still open at force-flush time.

Span categories (``cat``). The leaves the cross-host analytics sum:
``phase`` (the boundaries ``phase_seconds`` is built from), ``io``
(checkpoint shard and manifest writes), ``dcn_wait`` (barrier arrival
waits — the skew signal), ``dcn`` (payload collectives), ``stream``
(prefetch worker occupancy), ``compile`` (recompile events), and
``round`` (the eager per-round ``finalize`` envelope).
``round_summary()`` folds a round's spans of these into the schema-v12
``spans`` record sub-object (``utils/reporting.py``). The envelopes of
the in-memory record (:data:`ENVELOPE_CATS`: ``run`` the root, ``setup``
the set-up sections, ``iter`` the per-iteration ``round`` span, ``host``
for ``record``, ``checkpoint`` and ``valuation_audit``) enclose those
leaves, so they stay out of ``seconds_by_cat``, of the span counts and
of the stitcher's busy time: a leaf is counted once.

Thread-safe (the streaming prefetch worker emits occupancy spans from
its own thread; its spans have no parent on the main thread).
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import os
import threading

import jax
import numpy as np

from distributed_learning_simulator_tpu.telemetry import clock
from distributed_learning_simulator_tpu.telemetry.phases import (
    NullPhaseTimer,
    make_phase_timer,
)
from distributed_learning_simulator_tpu.telemetry.recompile import (
    DURATION_EVENTS,
    RecompileMonitor,
)

#: The round program's ``model_counts`` aux -> the counters they add to.
_COUNT_NAMES = {
    "moe_local_assignments": "local_expert_assignments",
    "moe_routed_tokens": "routed_tokens",
    "moe_overflows": "moe_overflows",
}
JOURNAL_VERSION = 1

#: Journal filename for a host, next to metrics.jsonl in the artifacts
#: (or ``span_dir``) directory. The stitcher globs this pattern.
JOURNAL_PATTERN = "spans_{host_id}.jsonl"


def journal_filename(host_id: int) -> str:
    return JOURNAL_PATTERN.format(host_id=int(host_id))


#: Categories of the spans that enclose other spans of the same round
#: (see the module docstring): recorded and journaled like any other,
#: left out of the per-round and per-run category sums and counts.
ENVELOPE_CATS = frozenset({"run", "setup", "iter", "host"})

_LAST_RUN: "SpanRecorder | None" = None


def last_run() -> "SpanRecorder | None":
    """The recorder of the most recent ``run_simulation`` call in this
    process (set-up list, ring and counters stay readable after the call
    returned and the journal closed); ``None`` if that call ran with
    ``telemetry_level='off'`` and ``span_trace='off'``, or none ran."""
    return _LAST_RUN


def union_seconds(intervals, lo: float = float("-inf"),
                  hi: float = float("inf")) -> float:
    """Length of the union of ``(start, end)`` intervals, clipped to
    ``[lo, hi]``: overlapping and nested intervals count once."""
    total, end = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, end), min(b, hi)
        if b > a:
            total += b - a
            end = b
    return total


def self_seconds(spans) -> dict[int, float]:
    """``{span id: self time}``: a span's duration minus the part of its
    interval that its child spans cover (children that overlap each
    other count once)."""
    children: dict[int, list] = {}
    for s in spans:
        if s.get("parent") is not None and "dur" in s:
            children.setdefault(s["parent"], []).append(
                (s["t0"], s["t0"] + s["dur"])
            )
    return {
        s["id"]: s["dur"] - union_seconds(
            children.get(s["id"], ()), s["t0"], s["t0"] + s["dur"]
        )
        for s in spans if "dur" in s and "id" in s
    }


def _annotation(name: str, cat: str, round_idx):
    """The profiler's view of a span: same name, ``cat`` and ``round`` as
    metadata. Inert (a flag check) while no profiler session runs."""
    if round_idx is None:
        return jax.profiler.TraceAnnotation(name, cat=cat)
    return jax.profiler.TraceAnnotation(name, cat=cat, round=int(round_idx))


class _SpanBox(dict):
    """What a span's body is handed: a dict of result attrs
    (``box["bytes"] = n``, merged into the span record) that is also the
    slot a phase parks its output in (``box.fence(value)``: waited on
    before the clock stops under ``telemetry_level='detailed'``)."""

    __slots__ = ("value",)

    def __init__(self):
        super().__init__()
        self.value = None

    def fence(self, value) -> None:
        self.value = value


class _Sections:
    """``section()``, over a tracer's own ``span()``."""

    _section = None  # the open section's context

    def section(self, name: str | None, cat: str = "setup") -> None:
        """Sequential top-level sections (set-up): close the open one,
        then open ``name`` (``None``: only close). The set-up code reads
        top to bottom, so a section lasts until the next begins."""
        if self._section is not None:
            ctx, self._section = self._section, None
            ctx.__exit__(None, None, None)
        if name is not None:
            self._section = self.span(name, cat)
            self._section.__enter__()


class SpanRecorder(_Sections):
    """Set-up list + bounded in-memory span ring + per-host JSONL journal.

    Hot-path cost is one inert profiler annotation, two clock reads, one
    dict build and a list/deque append under a lock; journal I/O happens
    only in ``flush()`` (once per round), at eager begins (a handful per
    round), and in the failure paths.
    """

    #: NullTracer's twin answers False: "is anything being recorded".
    recording = True
    #: span_trace='on' (set by :func:`start_run`): the journal, the
    #: flight recorder and the multihost seams are wanted too.
    journal = False

    def __init__(self, host_id: int = 0, n_hosts: int = 1,
                 capacity: int = 4096, flush_last_k: int = 64,
                 phases=None):
        if capacity < 1:
            raise ValueError(f"span buffer capacity must be >= 1: {capacity}")
        if flush_last_k < 1:
            raise ValueError(f"flush_last_k must be >= 1: {flush_last_k}")
        self.host_id = int(host_id)
        self.n_hosts = int(n_hosts)
        self.capacity = int(capacity)
        self.flush_last_k = int(flush_last_k)
        # Where ``span(..., phase=<name>)`` accumulates: the
        # ``phase_seconds`` of the records (inert at telemetry 'off').
        self.phases = phases if phases is not None else NullPhaseTimer()
        self.main_thread = threading.get_ident()
        self._lock = threading.Lock()
        self._tls = threading.local()
        # Set-up (until the first round completes) is never evicted;
        # the ring holds the newest ``capacity`` records after it.
        self._in_setup = True
        self._setup: list[dict] = []
        self._setup_flushed = 0
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._unflushed = 0  # newest ring entries not yet in a journal
        # The end of the newest record the ring has evicted (None: all
        # still held). Readers of a window check it (``evicted_until``).
        self._evicted_until: float | None = None
        # Counters: completed rounds with their stamps, completed
        # host_sync spans, and the jax.monitoring durations (counter,
        # t_end, secs) the run's monitor hands over from start() on.
        self.monitor = RecompileMonitor(on_event=self._on_duration)
        self._rounds = 0
        self._first_round_t: float | None = None
        self._round_stamps: collections.deque = collections.deque(
            maxlen=capacity
        )
        self._host_syncs = 0
        # Counts fixed when the run's programs are built (set_counter).
        self._build_counts: dict[str, int] = {}
        self._jax_setup: list[tuple] = []
        self._jax_ring: collections.deque = collections.deque(
            maxlen=capacity
        )
        self._root = None  # the root span's context, start() to finish()
        self._open: dict[int, dict] = {}
        self._next_id = 0
        self._dropped = 0
        self._round_agg: dict[int, dict] = {}
        # Skews measured after a round's record already shipped (the
        # checkpoint barrier runs post-emit): parked here and merged
        # into the NEXT round_summary — "the most recent checkpoint
        # barrier's skew", never silently dropped.
        self._pending_skews: dict[str, float] = {}
        # Run-level aggregate for the result dict's span_summary.
        self._run = {"count": 0, "by_cat": {}, "skews": {}}
        # The innermost span an exception unwound through: by the time
        # the crash handler calls flush_inflight, every context-managed
        # span has already closed on the unwind, so this is the only
        # record of WHERE the failure struck — stamped onto the flight
        # marker as ``in_span``.
        self._last_error: dict | None = None
        self._file = None
        self.journal_path: str | None = None
        self._closed = False

    # ------------------------------------------------------------------
    # journal attachment

    def attach(self, directory: str, clock_offset_s: float = 0.0,
               clock_uncertainty_s: float = 0.0) -> str:
        """Open ``spans_<host_id>.jsonl`` under ``directory`` and write
        the header line (clock anchors + alignment). Returns the path."""
        os.makedirs(directory, exist_ok=True)
        path = os.path.join(directory, journal_filename(self.host_id))
        # Anchor the monotonic epoch: sample wall and monotonic
        # back-to-back so (epoch_wall, epoch_mono) name the same instant
        # up to a few microseconds.
        epoch_wall = clock.wall()
        epoch_mono = clock.monotonic()
        header = {
            "kind": "header",
            "journal_version": JOURNAL_VERSION,
            "host_id": self.host_id,
            "n_hosts": self.n_hosts,
            "pid": os.getpid(),
            "epoch_wall": epoch_wall,
            "epoch_mono": epoch_mono,
            "clock_offset_s": float(clock_offset_s),
            "clock_uncertainty_s": float(clock_uncertainty_s),
            "span_trace": "on",
        }
        with self._lock:
            self._file = open(path, "w", encoding="utf-8")
            self.journal_path = path
            self._file.write(json.dumps(header) + "\n")
            self._file.flush()
        return path

    # ------------------------------------------------------------------
    # span emission

    def begin(self, name: str, cat: str, round_idx: int | None = None,
              eager: bool = False, **attrs) -> int:
        """Open a span; returns its id for :meth:`end`.

        ``eager=True`` writes an ``open`` journal line immediately and
        flushes it to the OS — the flight-recorder guarantee that a
        SIGKILL mid-span still leaves the span's identity on disk.
        """
        t0 = clock.monotonic()
        stack = self._stack()
        span = {
            "id": -1, "name": name, "cat": cat, "t0": t0,
            "parent": stack[-1] if stack else None,
            "thread": threading.get_ident(),
        }
        if round_idx is not None:
            span["round"] = int(round_idx)
        if attrs:
            span["attrs"] = attrs
        with self._lock:
            sid = self._next_id
            self._next_id += 1
            span["id"] = sid
            self._open[sid] = span
            if eager and self._file is not None:
                line = {"kind": "open", **{k: span[k] for k in span
                                           if k != "attrs"}}
                if attrs:
                    line["attrs"] = attrs
                self._file.write(json.dumps(line) + "\n")
                self._file.flush()
        stack.append(sid)
        return sid

    def end(self, span_id: int, **attrs) -> float:
        """Close a span; returns its duration in seconds. Extra attrs
        merge into the span record (e.g. measured skew on a wait)."""
        t1 = clock.monotonic()
        stack = self._stack()
        if stack and stack[-1] == span_id:
            stack.pop()
        elif span_id in stack:  # closed out of order
            stack.remove(span_id)
        with self._lock:
            span = self._open.pop(span_id, None)
            if span is None:
                return 0.0
            dur = t1 - span["t0"]
            span["dur"] = dur
            if attrs:
                span.setdefault("attrs", {}).update(attrs)
            self._append_locked(span)
            self._aggregate_locked(span)
        return dur

    @contextlib.contextmanager
    def span(self, name: str, cat: str, round_idx: int | None = None,
             eager: bool = False, phase: str | None = None, **attrs):
        """THE timing context of a boundary: profiler annotation, span
        record and (``phase=<name>``) the round's phase accumulation,
        from one clock read per edge. Yields a :class:`_SpanBox`."""
        box = _SpanBox()
        with _annotation(name, cat, round_idx):
            sid = self.begin(name, cat, round_idx=round_idx, eager=eager,
                             **attrs)
            try:
                yield box
            except BaseException as e:
                # Remember the innermost span this exception escaped
                # from — the span itself closes below (clean journals),
                # but the flight marker needs to name where the failure
                # struck.
                err = {"name": name, "cat": cat, "error": type(e).__name__}
                if round_idx is not None:
                    err["round"] = int(round_idx)
                with self._lock:
                    if self._last_error is None:
                        self._last_error = err
                raise
            finally:
                if phase is not None:
                    # 'detailed' waits for the parked output before the
                    # clock stops, so span and phase measure device time.
                    self.phases.fence(box)
                dur = self.end(sid, **box)
                if phase is not None:
                    self.phases.add(round_idx, phase, dur)

    def start(self) -> None:
        """Open the root span ``run`` and switch the monitor's
        ``jax.monitoring`` listener on: the first line of
        ``run_simulation``. Paired with :meth:`finish` in a
        ``try``/``finally`` there (no ``with``, no wrapper frame:
        PERF.md § 6, PR 25)."""
        self.monitor.listen()
        self._root = self.span("run", "run")
        self._root.__enter__()

    def finish(self) -> None:
        """Close the open section and the root span, switch the listener
        off, then drain and close the journal (the root is its last
        span line). Idempotent."""
        root, self._root = self._root, None
        if root is None:
            return
        try:
            self.section(None)
            root.__exit__(None, None, None)
        finally:
            self.monitor.stop()
            self.close()

    def round_done(self, round_idx: int, t: float) -> None:
        """Count a completed round at the program's own stamp ``t`` (the
        end of its ``round_seconds``). The first one ends set-up."""
        with self._lock:
            self._rounds += 1
            self._round_stamps.append((int(round_idx), t))
            if self._in_setup:
                self._in_setup = False
                self._first_round_t = t

    def set_counter(self, name: str, value: int) -> None:
        """Record a count that is fixed once, when the run's programs are
        built (``local_steps_unrolled``: how many local steps the round
        program holds unrolled, 0 on the scan path). It joins
        :meth:`counters` and goes to the journal as an ``event`` line of
        cat ``counter`` with the count under ``attrs.value``."""
        ev = {"kind": "event", "name": name, "cat": "counter",
              "t": clock.monotonic(), "attrs": {"value": int(value)}}
        with self._lock:
            self._build_counts[name] = int(value)
            self._append_locked(ev)

    def add_counts(self, counts: dict) -> None:
        """Add a round's fetched counts to the running counters of
        :meth:`counters` (the round program's ``model_counts`` aux: an
        expert layer's ``moe_local_assignments``, ``moe_routed_tokens``,
        ``moe_overflows`` become ``local_expert_assignments``,
        ``routed_tokens``, ``moe_overflows``; arrays are summed)."""
        with self._lock:
            for name, value in counts.items():
                name = _COUNT_NAMES.get(name)
                if name is not None:
                    self._build_counts[name] = self._build_counts.get(
                        name, 0
                    ) + int(np.sum(value))

    def _on_duration(self, counter: str, t_end: float,
                     seconds: float) -> None:
        """The monitor's ``on_event``: one tracing, lowering or backend
        compile (or cache load) ended at ``t_end``."""
        with self._lock:
            (self._jax_setup if self._in_setup else self._jax_ring).append(
                (counter, t_end, seconds)
            )

    def event(self, name: str, cat: str, round_idx: int | None = None,
              **attrs) -> None:
        """Instant event (zero-duration mark: recompile, dispatch)."""
        ev = {"kind": "event", "name": name, "cat": cat,
              "t": clock.monotonic()}
        if round_idx is not None:
            ev["round"] = int(round_idx)
        if attrs:
            ev["attrs"] = attrs
        with self._lock:
            self._append_locked(ev)
            self._run["count"] += 1
            if round_idx is not None:
                agg = self._agg_for_locked(round_idx)
                agg["count"] += 1

    def note_skew(self, round_idx: int, key: str, skew_ms: float) -> None:
        """Record a measured barrier skew (``spill_skew_ms`` /
        ``ckpt_skew_ms``) into the round's summary. Max-aggregated: the
        worst skew a round saw is the one that bounds its critical path."""
        with self._lock:
            agg = self._agg_for_locked(round_idx)
            prev = agg["skews"].get(key)
            if prev is None or skew_ms > prev:
                agg["skews"][key] = float(skew_ms)
            self._note_run_skew_locked(key, skew_ms)

    def note_pending_skew(self, key: str, skew_ms: float) -> None:
        """Like :meth:`note_skew` for a barrier that ran AFTER its
        round's record shipped (the checkpoint barrier): merged into the
        next :meth:`round_summary` instead of a specific round's."""
        with self._lock:
            prev = self._pending_skews.get(key)
            if prev is None or skew_ms > prev:
                self._pending_skews[key] = float(skew_ms)
            self._note_run_skew_locked(key, skew_ms)

    # ------------------------------------------------------------------
    # draining

    def flush(self) -> int:
        """Write completed spans/events not yet journaled. Returns the
        number of lines written (0 when unattached — set-up list and
        ring then are the in-memory record alone). Nothing is removed:
        the records stay readable (:meth:`spans`)."""
        with self._lock:
            if self._file is None:
                return 0
            recs = self._take_unflushed_locked()
            for rec in recs:
                self._file.write(json.dumps(self._line_locked(rec)) + "\n")
            if recs:
                self._file.flush()
            return len(recs)

    def flush_inflight(self, reason: str) -> int:
        """Force-flush for the failure paths (SIGTERM, quorum rejection,
        unhandled crash): last-K completed spans, a ``flight`` marker
        carrying ``reason``, then one ``inflight`` line per open span.
        Safe to call multiple times and with no journal attached."""
        with self._lock:
            if self._file is None or self._closed:
                return 0
            n = 0
            tail = self._take_unflushed_locked()[-self.flush_last_k:]
            for rec in tail:
                self._file.write(json.dumps(self._line_locked(rec)) + "\n")
                n += 1
            flight = {
                "kind": "flight", "reason": str(reason),
                "t": clock.monotonic(), "wall": clock.wall(),
            }
            if self._last_error is not None:
                flight["in_span"] = self._last_error
            self._file.write(json.dumps(flight) + "\n")
            n += 1
            for span in self._open.values():
                line = {"kind": "inflight", "inflight": True,
                        **{k: span[k] for k in span}}
                self._file.write(json.dumps(line) + "\n")
                n += 1
            self._file.flush()
            try:
                os.fsync(self._file.fileno())
            except OSError:
                pass
            return n

    def close(self) -> None:
        """Final flush + close the journal (idempotent); what was
        recorded stays readable."""
        self.flush()
        with self._lock:
            self._closed = True
            if self._file is not None:
                try:
                    self._file.flush()
                    self._file.close()
                finally:
                    self._file = None

    # ------------------------------------------------------------------
    # reading (the benchmark's metric readers, reports, tests)

    def spans(self) -> list[dict]:
        """Completed spans, set-up list first, then the ring (copies of
        the containers, not of the records: do not mutate)."""
        with self._lock:
            recs = self._setup + list(self._ring)
        return [r for r in recs if r.get("kind") != "event"]

    @property
    def evicted_until(self) -> float | None:
        """End of the newest record the ring has evicted; ``None`` while
        every span since the run's entry is still held. A reader whose
        window begins before it is reading a window with holes."""
        with self._lock:
            return self._evicted_until

    def round_stamps(self) -> list[tuple[int, float]]:
        """``(round, t)`` of the completed rounds still held (the newest
        ``capacity``), ``t`` on :func:`clock.monotonic`."""
        with self._lock:
            return list(self._round_stamps)

    def duration_events(self) -> list[tuple[str, float, float]]:
        """``(counter, t_end, seconds)`` per ``jax.monitoring`` duration
        event: all of set-up's, the newest ``capacity`` after it. Traces
        nest (a jitted function traced inside another's trace, or inside
        a lowering), so sum them as intervals ``(t_end - seconds,
        t_end)`` through :func:`union_seconds`, never as numbers."""
        with self._lock:
            return self._jax_setup + list(self._jax_ring)

    def counters(self) -> dict:
        """``trace_s`` / ``lower_s`` / ``compile_s`` as ``[before,
        after]`` the first round completed (unions of the events'
        intervals), ``host_syncs`` (completed ``host_sync`` spans),
        ``rounds`` (completed rounds) and what :meth:`set_counter` was
        given (``local_steps_unrolled``, ``client_axis_width``,
        ``head_backward_tied``, ``global_donated``, ``attention_window``,
        ``swa_keys_per_query_block``, ``fused_attention_layers``)."""
        events = self.duration_events()
        with self._lock:
            cut = self._first_round_t
            host_syncs = self._host_syncs
            rounds = self._rounds
            build_counts = dict(self._build_counts)
        cut = float("inf") if cut is None else cut
        out: dict = {}
        for key in DURATION_EVENTS.values():
            ivs = [(t - d, t) for k, t, d in events if k == key]
            out[key] = [
                union_seconds(iv for iv in ivs if iv[1] <= cut),
                union_seconds(iv for iv in ivs if iv[1] > cut),
            ]
        out["host_syncs"] = host_syncs
        out["rounds"] = rounds
        out.update(build_counts)
        return out

    # ------------------------------------------------------------------
    # per-round summary (schema-v12 `spans` sub-object)

    def round_summary(self, round_idx: int) -> dict:
        """Pop the round's aggregate as the metrics-record sub-object.
        Pending post-emit skews (checkpoint barrier) merge in here."""
        with self._lock:
            agg = self._round_agg.pop(int(round_idx), None)
            dropped = self._dropped
            pending = self._pending_skews
            self._pending_skews = {}
        rec = {
            "host_id": self.host_id,
            "hosts": self.n_hosts,
            "count": 0 if agg is None else int(agg["count"]),
        }
        if dropped:
            rec["dropped"] = int(dropped)
        if agg is not None:
            if agg["by_cat"]:
                rec["seconds_by_cat"] = {
                    k: round(v, 6) for k, v in sorted(agg["by_cat"].items())
                }
            rec["dcn_wait_s"] = round(agg["by_cat"].get("dcn_wait", 0.0), 6)
            rec["dcn_transfer_s"] = round(agg["by_cat"].get("dcn", 0.0), 6)
            skews = dict(agg["skews"])
        else:
            skews = {}
        for k, v in pending.items():
            if skews.get(k) is None or v > skews[k]:
                skews[k] = v
        if agg is not None or skews:
            rec["spill_skew_ms"] = skews.get("spill_skew_ms")
            rec["ckpt_skew_ms"] = skews.get("ckpt_skew_ms")
        return rec

    def run_summary(self) -> dict:
        """Whole-run aggregate for the result dict's ``span_summary``
        (bench.py's mhost leg and the 2-process tests read it)."""
        with self._lock:
            run = {
                "count": int(self._run["count"]),
                "dropped": int(self._dropped),
                "by_cat": dict(self._run["by_cat"]),
                "skews": dict(self._run["skews"]),
            }
        return {
            "host_id": self.host_id,
            "hosts": self.n_hosts,
            "journal_path": self.journal_path,
            "count": run["count"],
            "dropped": run["dropped"],
            "seconds_by_cat": {
                k: round(v, 6) for k, v in sorted(run["by_cat"].items())
            },
            "dcn_wait_s": round(run["by_cat"].get("dcn_wait", 0.0), 6),
            "dcn_transfer_s": round(run["by_cat"].get("dcn", 0.0), 6),
            "spill_skew_ms_max": run["skews"].get("spill_skew_ms"),
            "ckpt_skew_ms_max": run["skews"].get("ckpt_skew_ms"),
        }

    # ------------------------------------------------------------------
    # internals

    def _stack(self) -> list[int]:
        """Ids of the spans open on the calling thread, outermost first."""
        try:
            return self._tls.stack
        except AttributeError:
            self._tls.stack = []
            return self._tls.stack

    # (the rest: call with self._lock held)

    def _append_locked(self, rec: dict) -> None:
        if self._in_setup:
            self._setup.append(rec)
            return
        if len(self._ring) == self._ring.maxlen:
            # The oldest entry goes. It is lost to the journal only if
            # it never reached one (always, when none is attached).
            old = self._ring[0]
            self._evicted_until = (
                old["t"] if "t" in old else old["t0"] + old["dur"]
            )
            if self._unflushed >= len(self._ring):
                self._dropped += 1
                self._unflushed -= 1
        self._ring.append(rec)
        self._unflushed += 1

    def _take_unflushed_locked(self) -> list[dict]:
        """The records no journal holds yet, oldest first; marks them
        journaled."""
        recs = self._setup[self._setup_flushed:]
        self._setup_flushed = len(self._setup)
        if self._unflushed:
            recs.extend(itertools.islice(
                self._ring, len(self._ring) - self._unflushed, None
            ))
            self._unflushed = 0
        return recs

    def _agg_for_locked(self, round_idx: int) -> dict:
        return self._round_agg.setdefault(int(round_idx), {
            "count": 0, "by_cat": {}, "skews": {},
        })

    def _aggregate_locked(self, span: dict) -> None:
        cat = span.get("cat", "")
        dur = span.get("dur", 0.0)
        if span["name"] == "host_sync":
            self._host_syncs += 1
        if cat in ENVELOPE_CATS:
            return
        self._run["count"] += 1
        self._run["by_cat"][cat] = self._run["by_cat"].get(cat, 0.0) + dur
        rnd = span.get("round")
        if rnd is None:
            return
        agg = self._agg_for_locked(rnd)
        agg["count"] += 1
        agg["by_cat"][cat] = agg["by_cat"].get(cat, 0.0) + dur

    def _note_run_skew_locked(self, key: str, skew_ms: float) -> None:
        prev = self._run["skews"].get(key)
        if prev is None or skew_ms > prev:
            self._run["skews"][key] = float(skew_ms)

    @staticmethod
    def _line_locked(rec: dict) -> dict:
        if rec.get("kind") == "event":
            return rec
        return {"kind": "span", **rec}


class NullTracer(_Sections):
    """``telemetry_level='off'`` with ``span_trace='off'``: the same
    calls, no clock and no record. A boundary still enters its profiler
    annotation, so a ``profile_dir`` capture is labelled at any level."""

    recording = False
    journal = False
    phases = NullPhaseTimer()

    def start(self) -> None:
        return None

    def finish(self) -> None:
        self.section(None)

    @contextlib.contextmanager
    def span(self, name: str, cat: str, round_idx: int | None = None,
             eager: bool = False, phase: str | None = None, **attrs):
        with _annotation(name, cat, round_idx):
            yield _SpanBox()

    def round_done(self, round_idx: int, t: float) -> None:
        return None

    def set_counter(self, name: str, value: int) -> None:
        return None

    def add_counts(self, counts: dict) -> None:
        return None


def start_run(level: str, journal: bool, capacity: int = 4096,
              flush_last_k: int = 64) -> "SpanRecorder | NullTracer":
    """The tracer of one ``run_simulation`` call, made at its first
    line: a recorder (in memory only) whenever ``telemetry_level`` is
    not 'off' or ``span_trace`` is 'on' (``journal``), else the null
    twin. It becomes what :func:`last_run` returns."""
    global _LAST_RUN
    if level == "off" and not journal:
        _LAST_RUN = None
        return NullTracer()
    _LAST_RUN = SpanRecorder(
        capacity=capacity, flush_last_k=flush_last_k,
        phases=make_phase_timer(level),
    )
    _LAST_RUN.journal = journal
    return _LAST_RUN
