"""Span recorder unit tests: recorder, journals, stitcher.

Fast and device-free (scripts/trace_timeline.py deliberately imports no
jax; telemetry/spans.py touches it only for the inert profiler
annotation): parent/child and self-time arithmetic, the set-up list and
the span ring's bounds, one clock read per edge, the counters, the
journal line taxonomy, the flight-recorder guarantees (eager open-lines survive a
kill; ``flush_inflight`` names still-open spans), the off-gate
``config_hash`` invariance, and the cross-host stitcher on SYNTHETIC
two-host journals with a known clock offset — so the alignment math
((t - epoch_mono) + epoch_wall - clock_offset_s) is pinned by
arithmetic, not by a live 2-process run. The live integration (real
straggler attribution, real SIGKILL postmortem) is
tests/test_multihost.py's 2-process harness.
"""

import importlib.util
import json
import os
import sys

import pytest

from distributed_learning_simulator_tpu.config import ExperimentConfig
from distributed_learning_simulator_tpu.telemetry import clock, spans
from distributed_learning_simulator_tpu.telemetry.phases import PhaseTimer
from distributed_learning_simulator_tpu.telemetry.recompile import (
    DURATION_EVENTS,
)
from distributed_learning_simulator_tpu.telemetry.spans import (
    NullTracer,
    SpanRecorder,
    journal_filename,
    self_seconds,
    union_seconds,
)
from distributed_learning_simulator_tpu.utils.reporting import config_hash

_STITCHER = os.path.join(
    os.path.dirname(__file__), "..", "scripts", "trace_timeline.py"
)


@pytest.fixture(scope="module")
def tt():
    spec = importlib.util.spec_from_file_location(
        "trace_timeline", _STITCHER
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ----------------------------------------------------------------------
# recorder


def test_recorder_validates_bounds():
    with pytest.raises(ValueError):
        SpanRecorder(capacity=0)
    with pytest.raises(ValueError):
        SpanRecorder(flush_last_k=0)


def test_ring_is_bounded_and_counts_drops():
    rec = SpanRecorder(capacity=4)
    rec.round_done(0, clock.monotonic())  # set-up over: the ring is in use
    for _ in range(10):
        sid = rec.begin("s", "phase", round_idx=0)
        rec.end(sid)
    assert len(rec._ring) == 4
    summary = rec.round_summary(0)
    # Every end aggregated (the summary is not bounded by the ring) and
    # the overflow is reported, never silent.
    assert summary["count"] == 10
    assert summary["dropped"] == 6
    # Unattached flushes are safe no-ops.
    assert rec.flush() == 0
    assert rec.flush_inflight("sigterm") == 0


def test_setup_list_survives_a_ring_overflow():
    """Spans that end before the first round completes are set-up: kept
    in a list of their own, whole, however far the ring overflows
    afterwards."""
    rec = SpanRecorder(capacity=4)
    rec.start()
    rec.section("setup/data")
    rec.section("setup/model_init")
    rec.section(None)
    rec.round_done(0, clock.monotonic())
    assert rec.evicted_until is None
    for r in range(1, 50):
        with rec.span("round", "iter", round_idx=r):
            with rec.span("host_sync", "phase", round_idx=r):
                pass
    rec.finish()
    names = [s["name"] for s in rec.spans()]
    assert names[:2] == ["setup/data", "setup/model_init"]
    assert len(names) == 2 + 4  # the set-up list + a full ring
    assert names[-1] == "run"  # the root ends last, so the ring holds it
    assert rec.run_summary()["dropped"] == 2 * 49 + 1 - 4
    # The ring says up to when it has lost spans: the end of the newest
    # one evicted, just before the oldest it still holds began.
    oldest = rec.spans()[2]
    assert rec.evicted_until <= oldest["t0"] + oldest["dur"]
    assert rec.evicted_until > rec.spans()[1]["t0"]
    # The counters are run-wide, evicted spans included.
    assert rec.counters()["host_syncs"] == 49
    assert rec.counters()["rounds"] == 1


def test_parent_child_and_self_time():
    """A span's parent is the innermost span open on the same thread when
    it began; every span of one round carries that round; self time is
    the duration less what the children cover (overlaps counted once)."""
    rec = SpanRecorder()
    with rec.span("run", "run"):
        with rec.span("round", "iter", round_idx=5):
            with rec.span("dispatch", "phase", round_idx=5):
                pass
            # Pipelining: round 4's fetch inside round 5's iteration.
            with rec.span("finalize", "round", round_idx=4):
                with rec.span("host_sync", "phase", round_idx=4):
                    pass
    by = {s["name"]: s for s in rec.spans()}
    assert by["run"]["parent"] is None
    assert by["round"]["parent"] == by["run"]["id"]
    assert by["dispatch"]["parent"] == by["round"]["id"]
    assert by["finalize"]["parent"] == by["round"]["id"]
    assert by["host_sync"]["parent"] == by["finalize"]["id"]
    assert (by["host_sync"]["round"], by["finalize"]["round"]) == (4, 4)
    assert by["dispatch"]["round"] == by["round"]["round"] == 5
    assert {s["thread"] for s in rec.spans()} == {rec.main_thread}

    # Another thread's spans do not nest under the main thread's.
    import threading

    def worker():
        with rec.span("prefetch_upload", "stream", round_idx=5):
            pass

    with rec.span("outer", "phase"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    up = [s for s in rec.spans() if s["name"] == "prefetch_upload"][0]
    assert up["parent"] is None and up["thread"] != rec.main_thread

    # The arithmetic, on hand-made spans: children [1,3] and [2,4]
    # overlap, so they cover 3 of the parent's [0,10]; a child that
    # sticks out past its parent is clipped to it.
    made = [
        {"id": 0, "parent": None, "t0": 0.0, "dur": 10.0},
        {"id": 1, "parent": 0, "t0": 1.0, "dur": 2.0},
        {"id": 2, "parent": 0, "t0": 2.0, "dur": 2.0},
        {"id": 3, "parent": 0, "t0": 9.0, "dur": 5.0},
        {"id": 4, "parent": 2, "t0": 2.5, "dur": 0.5},
    ]
    own = self_seconds(made)
    assert own[0] == pytest.approx(10.0 - 3.0 - 1.0)
    assert own[1] == 2.0 and own[2] == 1.5 and own[4] == 0.5
    assert union_seconds([(1, 3), (2, 4), (6, 7)]) == 4.0
    assert union_seconds([(1, 3), (2, 4), (6, 7)], lo=2.5, hi=6.5) == 2.0
    assert union_seconds([]) == 0.0


def test_one_clock_read_per_edge_feeds_span_and_phase(monkeypatch):
    """One boundary = one call: the span record and the round's phase
    accumulation come from the same two clock reads."""
    reads = []
    real = clock.monotonic

    def counting():
        reads.append(real())
        return reads[-1]

    monkeypatch.setattr(clock, "monotonic", counting)
    rec = SpanRecorder(phases=PhaseTimer(fence=False))
    with rec.span("dispatch", "phase", round_idx=2, phase="client_step"):
        pass
    with rec.span("record", "host", round_idx=2):
        pass
    assert len(reads) == 4
    span = rec.spans()[0]
    assert (span["t0"], span["t0"] + span["dur"]) == pytest.approx(
        (reads[0], reads[1])
    )
    # The phase got exactly the span's duration, under the PHASE's name;
    # a span without ``phase=`` feeds none.
    assert rec.phases.take(2) == {"client_step": span["dur"]}


def test_detailed_fences_before_the_clock_stops():
    """``phase=`` spans wait for what the body parked (``box.fence``)
    under a fencing PhaseTimer, so span and phase hold device time."""

    class Slow:
        waited = False

    import jax

    real = jax.block_until_ready
    try:
        jax.block_until_ready = lambda v: setattr(Slow, "waited", True)
        rec = SpanRecorder(phases=PhaseTimer(fence=True))
        with rec.span("dispatch", "phase", round_idx=0,
                      phase="client_step") as box:
            box.fence(object())
        assert Slow.waited
        Slow.waited = False
        with rec.span("record", "host", round_idx=0) as box:
            box.fence(object())  # not a phase: never waited on
        assert not Slow.waited
    finally:
        jax.block_until_ready = real


def test_counters_split_at_the_first_round_and_union_nested_traces():
    """jax's tracing events nest (a jitted function traced inside
    another's trace): the counters are unions of the events' intervals,
    split before/after the first round completes."""
    rec = SpanRecorder()
    trace, lower, comp = DURATION_EVENTS

    def at(t, event, dur):
        # As jax.monitoring calls the run's one listener, the monitor's.
        real = clock.monotonic
        clock.monotonic = lambda: t
        try:
            rec.monitor._on_duration(event, dur)
        finally:
            clock.monotonic = real

    at(1.0, trace, 0.5)      # inner trace [0.5, 1.0]
    at(2.0, trace, 2.0)      # outer trace [0.0, 2.0] contains it
    at(3.0, lower, 1.0)
    at(5.0, comp, 2.0)
    at(5.0, "/jax/some/other/event", 9.0)  # not ours
    rec.round_done(0, 6.0)
    at(7.0, comp, 0.25)
    got = rec.counters()
    assert got["trace_s"] == [2.0, 0.0]
    assert got["lower_s"] == [1.0, 0.0]
    assert got["compile_s"] == [2.0, 0.25]
    assert got["rounds"] == 1 and got["host_syncs"] == 0
    assert [k for k, _, _ in rec.duration_events()] == [
        "trace_s", "trace_s", "lower_s", "compile_s", "compile_s",
    ]
    assert rec.round_stamps() == [(0, 6.0)]


def test_null_tracer_is_inert(monkeypatch):
    """telemetry 'off' with span_trace 'off': the same calls, no clock
    read and nothing recorded (the body's fence box is a dead slot)."""
    monkeypatch.setattr(
        clock, "monotonic",
        lambda: pytest.fail("the null tracer read the clock"),
    )
    tracer = NullTracer()
    tracer.start()
    tracer.section("setup/data")
    with tracer.span("dispatch", "phase", round_idx=0,
                     phase="client_step") as box:
        box.fence(object())
    tracer.round_done(0, 0.0)
    tracer.finish()
    assert tracer._section is None
    assert not tracer.recording and not tracer.phases.enabled
    assert tracer.phases.take(0) is None


def test_start_run_and_last_run():
    assert isinstance(spans.start_run("off", False), NullTracer)
    assert spans.last_run() is None
    rec = spans.start_run("basic", False, capacity=7)
    assert spans.last_run() is rec and rec.capacity == 7
    assert rec.phases.enabled and not rec.phases._fence
    assert spans.start_run("detailed", False).phases._fence
    on = spans.start_run("off", True)  # span_trace at level 'off'
    assert spans.last_run() is on and not on.phases.enabled


def test_flush_writes_setup_and_keeps_records_readable(tmp_path):
    """The journal gets set-up spans too (recorded before it was
    attached), and a flush removes nothing from memory."""
    rec = SpanRecorder()
    rec.start()
    rec.section("setup/data")
    rec.section(None)
    path = rec.attach(str(tmp_path))
    rec.round_done(0, clock.monotonic())
    with rec.span("round", "iter", round_idx=1):
        pass
    assert rec.flush() == 2
    assert rec.flush() == 0
    rec.finish()
    lines = [json.loads(l) for l in open(path)]
    assert [l["name"] for l in lines if l["kind"] == "span"] == [
        "setup/data", "round", "run",
    ]
    assert all("parent" in l and "thread" in l
               for l in lines if l["kind"] == "span")
    assert [s["name"] for s in rec.spans()] == [
        "setup/data", "round", "run",
    ]


def test_journal_lines_and_round_summary(tmp_path):
    rec = SpanRecorder(host_id=3, n_hosts=4)
    path = rec.attach(str(tmp_path), clock_offset_s=0.25,
                      clock_uncertainty_s=0.001)
    assert os.path.basename(path) == journal_filename(3) == "spans_3.jsonl"
    with rec.span("client_step", "phase", round_idx=7) as extra:
        extra["bytes"] = 123
    rec.event("round_fn", "compile", round_idx=7, seconds=0.5)
    rec.note_skew(7, "spill_skew_ms", 12.5)
    rec.note_skew(7, "spill_skew_ms", 8.0)  # max-aggregated: keeps 12.5
    rec.note_pending_skew("ckpt_skew_ms", 3.25)
    assert rec.flush() == 2
    rec.close()

    lines = [json.loads(l) for l in open(path)]
    header = lines[0]
    assert header["kind"] == "header"
    assert header["journal_version"] == 1
    assert header["host_id"] == 3 and header["n_hosts"] == 4
    assert header["clock_offset_s"] == 0.25
    assert header["clock_uncertainty_s"] == 0.001
    assert header["epoch_wall"] > 0 and header["epoch_mono"] >= 0
    kinds = [l["kind"] for l in lines[1:]]
    assert kinds == ["span", "event"]
    span = lines[1]
    assert span["name"] == "client_step" and span["cat"] == "phase"
    assert span["round"] == 7 and span["dur"] >= 0
    assert span["attrs"]["bytes"] == 123

    summary = rec.round_summary(7)
    assert summary["host_id"] == 3 and summary["hosts"] == 4
    assert summary["count"] == 2  # span + event
    assert summary["seconds_by_cat"]["phase"] >= 0
    assert summary["spill_skew_ms"] == 12.5
    # Pending (post-emit checkpoint barrier) skew merged in here.
    assert summary["ckpt_skew_ms"] == 3.25
    # ...and popped: the next round doesn't re-report it.
    assert "ckpt_skew_ms" not in rec.round_summary(8)


def test_eager_open_line_survives_kill(tmp_path, tt):
    """The hard-kill guarantee: an eager begin's open-line is on disk
    BEFORE the span body runs, so a SIGKILL'd process still names the
    span it died inside — no cleanup code required."""
    rec = SpanRecorder(host_id=0)
    path = rec.attach(str(tmp_path))
    rec.begin("finalize", "round", round_idx=2, eager=True)
    # No end(), no flush(), no close(): the process "dies" here. Emulate
    # the torn tail a kill mid-write can leave behind, too.
    with open(path, "a") as f:
        f.write('{"kind": "span", "truncated')

    j = tt.load_journal(path)
    assert len(j["unmatched_opens"]) == 1
    assert j["unmatched_opens"][0]["name"] == "finalize"
    assert j["unmatched_opens"][0]["round"] == 2
    summary = tt.summarize([j])
    dead = [p for p in summary["postmortem"] if p["kind"] == "died_inside"]
    assert [p["name"] for p in dead] == ["finalize"]


def test_flush_inflight_names_open_spans(tmp_path, tt):
    """The soft-failure path (SIGTERM / quorum rejection / crash):
    last-K completed spans + a flight marker + one inflight line per
    still-open span."""
    rec = SpanRecorder(host_id=1, flush_last_k=2)
    path = rec.attach(str(tmp_path))
    for i in range(5):
        sid = rec.begin(f"done_{i}", "phase", round_idx=0)
        rec.end(sid)
    rec.begin("spill_wait", "dcn_wait", round_idx=0, eager=True)
    n = rec.flush_inflight("quorum_rejected")
    # last-K completed (2) + flight marker + 1 inflight line.
    assert n == 4
    lines = [json.loads(l) for l in open(path)]
    flights = [l for l in lines if l["kind"] == "flight"]
    assert flights and flights[0]["reason"] == "quorum_rejected"
    inflight = [l for l in lines if l["kind"] == "inflight"]
    assert [l["name"] for l in inflight] == ["spill_wait"]
    assert inflight[0]["inflight"] is True
    # The ring drained: only the last-K completed spans made it out.
    spans = [l for l in lines if l["kind"] == "span"]
    assert [s["name"] for s in spans] == ["done_3", "done_4"]

    summary = tt.summarize([tt.load_journal(path)])
    got = [p for p in summary["postmortem"] if p["kind"] == "inflight"]
    assert [p["name"] for p in got] == ["spill_wait"]


def test_run_summary_totals(tmp_path):
    rec = SpanRecorder(host_id=0, n_hosts=2)
    rec.attach(str(tmp_path))
    for rnd in range(3):
        sid = rec.begin("spill_wait", "dcn_wait", round_idx=rnd)
        rec.end(sid)
        rec.note_skew(rnd, "spill_skew_ms", 10.0 * (rnd + 1))
        rec.round_summary(rnd)
        rec.flush()
    run = rec.run_summary()
    rec.close()
    assert run["count"] == 3
    assert run["spill_skew_ms_max"] == 30.0
    assert run["ckpt_skew_ms_max"] is None
    assert run["journal_path"] == os.path.join(
        str(tmp_path), "spans_0.jsonl"
    )


# ----------------------------------------------------------------------
# off-gate: span knobs must not move config_hash at their off defaults


def test_span_trace_off_gate_config_hash():
    base = config_hash(ExperimentConfig())
    # Off-gated knobs at non-default values change nothing while the
    # feature is off — the exact pre-feature hash (byte-identity
    # contract, utils/reporting.config_hash).
    assert config_hash(ExperimentConfig(span_buffer_size=7)) == base
    assert config_hash(ExperimentConfig(span_flush_last_k=2)) == base
    # span_dir is a non-program output path: hash-exempt even when on.
    on = config_hash(ExperimentConfig(span_trace="on"))
    assert on != base
    assert config_hash(
        ExperimentConfig(span_trace="on", span_dir="/tmp/elsewhere")
    ) == on


def test_span_config_validation():
    with pytest.raises(ValueError, match="span_trace"):
        ExperimentConfig(span_trace="banana").validate()
    with pytest.raises(ValueError, match="span_buffer_size"):
        ExperimentConfig(span_buffer_size=0).validate()
    with pytest.raises(ValueError, match="span_flush_last_k"):
        ExperimentConfig(span_flush_last_k=0).validate()


# ----------------------------------------------------------------------
# stitcher on synthetic two-host journals with a KNOWN clock offset


def _write_journal(path, host_id, epoch_wall, epoch_mono, offset,
                   lines):
    with open(path, "w") as f:
        f.write(json.dumps({
            "kind": "header", "journal_version": 1, "host_id": host_id,
            "n_hosts": 2, "pid": 1000 + host_id,
            "epoch_wall": epoch_wall, "epoch_mono": epoch_mono,
            "clock_offset_s": offset, "clock_uncertainty_s": 0.0002,
            "span_trace": "on",
        }) + "\n")
        for line in lines:
            f.write(json.dumps(line) + "\n")


@pytest.fixture()
def two_host_dir(tmp_path):
    """Two synthetic journals describing the SAME true timeline.

    Host 0: wall epoch 1000.0 at monotonic 50.0, offset 0 (it IS the
    reference). Host 1: its wall clock runs 3.5 s AHEAD of host 0's
    (offset +3.5) and its monotonic epoch is 20.0 at its wall 1003.5 —
    i.e. the same true instant as host 0's epoch. A true host-0-wall
    time T is therefore monotonic T-950 on host 0 and T-983.5 on host 1,
    and both must align back to T exactly.

    The round-0 spill barrier: host 1 arrives 0.4 s late, so host 0's
    wait span is 0.5 s long vs host 1's 0.1 s, and both record the
    measured 400 ms skew. Host 1 also carries 3x host 0's busy time
    (the critical-path signal) and an unmatched open (it "died" inside
    round 1's finalize).
    """

    def h0(t):  # host-0 monotonic stamp for true wall time t
        return (t - 1000.0) + 50.0

    def h1(t):  # host-1 monotonic stamp for the same true instant
        return (t + 3.5 - 1003.5) + 20.0

    _write_journal(
        tmp_path / "spans_0.jsonl", 0, 1000.0, 50.0, 0.0,
        [
            {"kind": "span", "id": 0, "name": "client_step",
             "cat": "phase", "round": 0, "t0": h0(1008.0), "dur": 1.0},
            {"kind": "span", "id": 1, "name": "spill_wait",
             "cat": "dcn_wait", "round": 0, "t0": h0(1009.5), "dur": 0.5,
             "attrs": {"skew_ms": 400.0}},
            {"kind": "span", "id": 2, "name": "spill_xfer", "cat": "dcn",
             "round": 0, "t0": h0(1010.0), "dur": 0.05,
             "attrs": {"bytes": 4096}},
            {"kind": "event", "name": "dispatch", "cat": "dispatch",
             "round": 0, "t": h0(1008.0)},
        ],
    )
    _write_journal(
        tmp_path / "spans_1.jsonl", 1, 1003.5, 20.0, 3.5,
        [
            {"kind": "span", "id": 0, "name": "client_step",
             "cat": "phase", "round": 0, "t0": h1(1006.5), "dur": 3.0},
            {"kind": "span", "id": 1, "name": "spill_wait",
             "cat": "dcn_wait", "round": 0, "t0": h1(1009.9), "dur": 0.1,
             "attrs": {"skew_ms": 400.0}},
            {"kind": "open", "id": 2, "name": "finalize", "cat": "round",
             "round": 1, "t0": h1(1010.2)},
        ],
    )
    return tmp_path


def test_stitcher_aligns_known_offset(two_host_dir, tt):
    paths = tt.find_journals([str(two_host_dir)])
    assert [os.path.basename(p) for p in paths] == [
        "spans_0.jsonl", "spans_1.jsonl"
    ]
    journals = [tt.load_journal(p) for p in paths]
    a0 = tt.aligner(journals[0]["header"])
    a1 = tt.aligner(journals[1]["header"])
    # Both hosts' stamps of the same true instant align identically
    # despite different monotonic epochs AND the 3.5 s wall offset.
    t0_wait_end = journals[0]["spans"][1]  # host 0 spill_wait
    t1_wait_end = journals[1]["spans"][1]  # host 1 spill_wait
    h0_arrival = a0(t0_wait_end["t0"])
    h1_arrival = a1(t1_wait_end["t0"])
    assert h0_arrival == pytest.approx(1009.5, abs=1e-9)
    assert h1_arrival == pytest.approx(1009.9, abs=1e-9)
    # Without the offset correction host 1 would land 3.5 s wrong.
    naive = (t1_wait_end["t0"] - journals[1]["header"]["epoch_mono"]) \
        + journals[1]["header"]["epoch_wall"]
    assert naive == pytest.approx(1013.4, abs=1e-9)


def test_stitcher_summary_attributes_straggler(two_host_dir, tt):
    journals = [tt.load_journal(p)
                for p in tt.find_journals([str(two_host_dir)])]
    summary = tt.summarize(journals)
    # Barrier skew: both hosts measured the same 400 ms allgather skew;
    # the slowest host is the one that waited LEAST (it arrived last).
    entry = summary["rounds"]["0"]["spill_wait"]
    assert entry["skew_ms"] == 400.0
    assert entry["slowest_host"] == 1
    assert entry["waits"] == {0: 0.5, 1: 0.1}
    # Critical-path share: host 1 carries 3.0 of the 4.05 busy seconds.
    t0, t1 = summary["totals"]["0"], summary["totals"]["1"]
    assert t0["busy_s"] == pytest.approx(1.05)
    assert t1["busy_s"] == pytest.approx(3.0)
    assert t1["critical_path_share"] == pytest.approx(3.0 / 4.05, abs=1e-3)
    assert t0["dcn_wait_s"] == pytest.approx(0.5)
    # Postmortem: host 1's unmatched open names the span it died inside.
    dead = [p for p in summary["postmortem"]
            if p["kind"] == "died_inside"]
    assert [(p["host_id"], p["name"]) for p in dead] == [(1, "finalize")]
    # --host filter keeps the summary single-host.
    only0 = tt.summarize(journals, host=0)
    assert [h["host_id"] for h in only0["hosts"]] == [0]
    assert only0["postmortem"] == []


def test_stitcher_counts_a_checkpoint_round_once(tmp_path, tt):
    """A checkpoint round as the simulator and the sharded checkpoint
    writer emit it: the in-memory envelopes (`round` per iteration,
    `record`, `checkpoint`) enclose the leaves the cross-host analytics
    sum, so they are in the journal but in neither the stitcher's busy
    time nor the v12 ``seconds_by_cat``: busy = the sum of the leaf
    spans, the barrier wait is wait (not busy), ``round`` is the
    ``finalize`` envelope alone."""
    rec = SpanRecorder(host_id=1, n_hosts=2)
    path = rec.attach(str(tmp_path))
    rec.start()
    rec.section("setup/model_init")
    rec.section(None)
    with rec.span("round", "iter", round_idx=3):
        with rec.span("dispatch", "phase", round_idx=3,
                      phase="client_step"):
            pass
        with rec.span("finalize", "round", round_idx=3, eager=True):
            with rec.span("host_sync", "phase", round_idx=3,
                          phase="host_sync"):
                pass
            with rec.span("record", "host", round_idx=3):
                pass
            with rec.span("checkpoint", "host", round_idx=3):
                with rec.span("ckpt_shard_write", "io", round_idx=3):
                    pass
                with rec.span("ckpt_barrier_wait", "dcn_wait",
                              round_idx=3, eager=True):
                    pass
                with rec.span("ckpt_manifest", "io", round_idx=3):
                    pass
    summary = rec.round_summary(3)
    run = rec.run_summary()
    rec.finish()

    by = {s["name"]: s["dur"] for s in rec.spans()}
    leaves = ("dispatch", "host_sync", "ckpt_shard_write", "ckpt_manifest")
    journal = tt.load_journal(path)
    assert {s["name"] for s in journal["spans"]} == set(by)  # all there
    host = tt.summarize([journal])["totals"]["1"]
    assert host["busy_s"] == pytest.approx(
        sum(by[n] for n in leaves), abs=1e-6
    )
    assert host["dcn_wait_s"] == pytest.approx(
        by["ckpt_barrier_wait"], abs=1e-6
    )

    assert set(summary["seconds_by_cat"]) == {
        "phase", "round", "io", "dcn_wait",
    }
    assert summary["seconds_by_cat"]["io"] == pytest.approx(
        by["ckpt_shard_write"] + by["ckpt_manifest"], abs=2e-6
    )
    assert summary["seconds_by_cat"]["round"] == pytest.approx(
        by["finalize"], abs=1e-6
    )
    assert summary["dcn_wait_s"] == pytest.approx(
        by["ckpt_barrier_wait"], abs=1e-6
    )
    assert summary["count"] == len(leaves) + 2  # + finalize, the barrier
    assert run["count"] == summary["count"]
    assert set(run["seconds_by_cat"]) == set(summary["seconds_by_cat"])


def test_stitcher_chrome_trace(two_host_dir, tt):
    journals = [tt.load_journal(p)
                for p in tt.find_journals([str(two_host_dir)])]
    trace = tt.chrome_trace(journals)
    evs = trace["traceEvents"]
    spans = [e for e in evs if e["ph"] == "X" and not (
        e.get("args") or {}).get("inflight")]
    # Cross-host ordering on the merged timeline: host 1's client_step
    # starts 1.5 s before host 0's (true times 1006.5 vs 1008.0) even
    # though its RAW monotonic stamp is smaller by a different amount.
    cs = {e["pid"]: e["ts"] for e in spans if e["name"] == "client_step"}
    assert cs[0] - cs[1] == pytest.approx(1.5e6, abs=1.0)
    # The trace origin is the earliest aligned stamp -> ts >= 0 always.
    assert min(e["ts"] for e in evs if "ts" in e) >= 0
    # Host 1's unmatched open renders as an explicitly-marked inflight
    # slice so the kill moment is visible in perfetto.
    inflight = [e for e in evs if (e.get("args") or {}).get("inflight")]
    assert [e["name"] for e in inflight] == ["finalize"]
    # Instant events keep their scope marker.
    marks = [e for e in evs if e["ph"] == "i"]
    assert marks and all(e["s"] == "t" for e in marks)


def test_stitcher_cli(two_host_dir, tt, tmp_path):
    import subprocess

    out = tmp_path / "trace.json"
    proc = subprocess.run(
        [sys.executable, _STITCHER, str(two_host_dir),
         "--out", str(out), "--json"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    summary = json.loads(proc.stdout)
    assert summary["rounds"]["0"]["spill_wait"]["slowest_host"] == 1
    trace = json.loads(out.read_text())
    assert trace["traceEvents"]
    # No journals -> exit 2, not a stack trace.
    empty = tmp_path / "empty"
    empty.mkdir()
    proc = subprocess.run(
        [sys.executable, _STITCHER, str(empty)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2


def test_flight_marker_names_errored_span(tmp_path, tt):
    """A crash that unwinds through span context managers closes them
    before the flight flush — the flight marker must still name the
    innermost span the exception escaped from."""
    rec = SpanRecorder(host_id=0)
    path = rec.attach(str(tmp_path))
    with pytest.raises(RuntimeError):
        with rec.span("finalize", "round", round_idx=3):
            with rec.span("spill_xfer", "dcn", round_idx=3):
                raise RuntimeError("peer died")
    rec.flush_inflight("crash")
    lines = [json.loads(ln) for ln in open(path)]
    flight = [ln for ln in lines if ln["kind"] == "flight"][0]
    assert flight["in_span"] == {"name": "spill_xfer", "cat": "dcn",
                                 "error": "RuntimeError", "round": 3}
    summary = tt.summarize([tt.load_journal(path)])
    fl = [p for p in summary["postmortem"] if p["kind"] == "flight"][0]
    assert fl["name"] == "spill_xfer" and fl["round"] == 3
    assert fl["error"] == "RuntimeError"
    assert "spill_xfer" in tt.render_text(summary)
