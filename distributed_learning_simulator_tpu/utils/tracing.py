"""Tracing/profiling: jax.profiler integration.

The reference has NO profiling instrumentation (SURVEY §5: the only
performance-adjacent output is compression-ratio logging). This module
exceeds parity: an opt-in programmatic profiler session writing an XPlane
trace directory, the reductions of such a trace (device-op ledgers and
rankings), and :func:`attribute_idle_gaps`, which names the device's idle
gaps by the program's own host spans: every boundary the span recorder
times (telemetry/spans.py) is also a ``TraceAnnotation`` of the same name,
visible in TensorBoard/Perfetto on the device trace's clock.

Usage: set ``config.profile_dir`` — the simulator wraps the run in
``start_trace``/``stop_trace``.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re

import jax


def iter_device_ops(trace_dir: str):
    """Yield device-lane op events from a jax.profiler trace directory.

    The ONE copy of the event-selection rule (shared by
    :func:`parse_device_trace` and the profiling scripts): complete ('X')
    events carrying XLA op annotations (``long_name`` or
    ``raw_bytes_accessed``), with parent ``while``/``jit(...)`` frames
    excluded — those wrap their children's time and would double count.
    Missing/empty trace dirs yield nothing rather than raising.

    Two assumptions callers must hold (ADVICE r4):

    * ``trace_dir`` must hold exactly ONE profiling session. Every
      ``*.trace.json.gz`` under the directory is summed, so a reused
      directory accumulates stale sessions into the totals. bench.py's
      proxy uses a fresh ``TemporaryDirectory`` per run; the profiling
      scripts ``rm -rf`` their target first.
    * Parent-frame exclusion is by the ``while``/``jit(`` name prefixes —
      the two wrapper frames XLA emits for these programs (whole-program
      jit frame, round/epoch/step ``while`` loops). A program whose
      byte-carrying ops sit under differently-named wrapper frames that
      also carry ``raw_bytes_accessed`` would double count; if a new
      wrapper family appears, extend the prefix list and re-baseline the
      proxy totals.
    """
    paths = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*",
                     "*.trace.json.gz")
    )
    for path in sorted(paths, key=os.path.getmtime):
        with gzip.open(path, "rt") as f:
            data = json.load(f)
        for ev in data.get("traceEvents", []):
            if ev.get("ph") != "X":
                continue
            args = ev.get("args") or {}
            if "long_name" not in args and "raw_bytes_accessed" not in args:
                continue
            name = ev.get("name", "")
            if name.startswith("while") or name.startswith("jit("):
                continue
            yield ev


def parse_device_trace(trace_dir: str) -> dict:
    """Aggregate device-op statistics from a jax.profiler trace directory.

    Returns ``{"device_ms", "bytes_gb", "op_count"}`` summed over
    :func:`iter_device_ops`. ``bytes_gb`` sums XLA's ``raw_bytes_accessed``
    — a DETERMINISTIC function of the compiled program (identical across
    runs of the same program on the same shapes), which makes it the
    environment-robust regression proxy bench.py emits: host contention
    moves wall-clock but cannot move the bytes the program accesses.
    CPU traces without byte annotations report zero bytes.
    """
    device_us = 0.0
    bytes_total = 0.0
    op_count = 0
    for ev in iter_device_ops(trace_dir):
        args = ev.get("args") or {}
        device_us += float(ev.get("dur", 0.0))
        bytes_total += float(args.get("raw_bytes_accessed", 0) or 0)
        op_count += 1
    return {
        "device_ms": device_us / 1e3,
        "bytes_gb": bytes_total / 2**30,
        "op_count": op_count,
    }


# Stage-attribution rules for the flagship ResNet-18 chunk-40 program
# (promoted from scripts/trace_categories.py, which is now a thin CLI
# wrapper): shape signatures in ``long_name`` -> pipeline stage. Ordered;
# first match wins. These are program-specific by design — the generic
# op-CLASS classification the cost model uses is :func:`classify_op`.
STAGE_RULES = [
    ("s4_wgrad", r"3,3,512,512.*fusion\(|fusion.*= f32\[3,3,512,512\]"),
    ("s3_wgrad", r"= f32\[3,3,256,256\]"),
    ("s2_wgrad", r"= f32\[3,3,128,128\]"),
    ("s1_wgrad", r"= f32\[3,3,128,40,128\]|= f32\[3,4,3,40,128\]|= f32\[3,2,128,40,"),
    ("stage4", r"4,4,512|2,2,512"),
    ("stage3", r"8,8,256"),
    ("stage2", r"16,16,128"),
    # stage-1 folded activations: NHWC [.., 32, 16, 128] (rounds 3-4) or
    # HWNC [32, 16, .., 128] (round 5); packed kernels/grads either way.
    ("stage1f", r"32,16,128|32,16,40,25,128|32,16,1000,128"
                r"|3,3,128,40,128|3,4,3,40,128"),
    ("dense/head", r"512,10|,10\]"),
    ("decode", r"u8\[|s32\["),
]

# Generic HLO op classes for the roofline cost model
# (telemetry/costmodel.py): every traced device op lands in exactly one.
OP_CLASSES = (
    "matmul_conv",   # MXU work: dots, convolutions, their fusions
    "elementwise",   # VPU work: loop/input fusions, reduces, converts
    "copy_layout",   # pure data movement: copies, transposes, bitcasts
    "collective",    # cross-chip: all-reduce/-gather/-to-all, permutes
    "decode",        # uint8 shard decode (compact_client_data path)
    "other",
)

_COLLECTIVE_MARKS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)
_COPY_PREFIXES = ("copy", "transpose", "bitcast")
# "convolution", not "conv": XLA's elementwise converts
# ("convert_reduce_fusion") must not read as MXU work.
_MATMUL_MARKS = ("convolution", "dot", "einsum", "gemm", "matmul")


def classify_op(name: str, long_name: str = "") -> str:
    """Map one device op to its :data:`OP_CLASSES` bucket.

    Classification reads the op NAME first (XLA names fusions after their
    root/hero op: ``convolution_convert_fusion``, ``loop_reduce_fusion``,
    ``all-reduce.1``) and falls back to ``long_name`` markers. Order
    matters and is part of the contract (tests/test_tracing.py):
    collectives before matmul (an all-reduce OF conv grads is collective
    volume, not MXU work), decode before elementwise (the u8 shard
    decode is its own byte budget), copies only by name PREFIX (a
    ``fusion`` whose long_name merely mentions copy is not a copy).
    """
    lowered = name.lower()
    if any(m in lowered for m in _COLLECTIVE_MARKS):
        return "collective"
    if lowered.startswith(_COPY_PREFIXES):
        return "copy_layout"
    if "u8[" in long_name:
        # The compact_client_data shard decode specifically — s32 is NOT
        # a decode mark here: eval argmax outputs and cohort-index
        # streams carry s32 and must keep their own class (STAGE_RULES
        # keeps the wider u8|s32 rule for the flagship stage map).
        return "decode"
    if any(m in lowered for m in _MATMUL_MARKS) or (
        "dot_general" in long_name or "convolution" in long_name
    ):
        return "matmul_conv"
    if lowered.startswith(("fusion", "loop_", "input_", "reduce", "convert",
                           "broadcast", "select", "add", "multiply",
                           "subtract", "compare", "iota", "rng")):
        return "elementwise"
    return "other"


def categorize_long_name(long_name: str, rules=STAGE_RULES) -> str:
    """First-match rule category of one op's ``long_name`` (the stage
    attribution scripts/trace_categories.py prints); "other" when no
    rule matches."""
    for cat, pat in rules:
        if re.search(pat, long_name):
            return cat
    return "other"


def categorize_ops(trace_dir: str, rules=None) -> dict[str, dict]:
    """Categorized op LEDGER of a trace directory — the cost model's
    input (telemetry/costmodel.py) and the shared core of
    scripts/trace_categories.py.

    One pass over :func:`iter_device_ops` (the SAME selection rule as the
    bench proxy — wrapper ``while``/``jit(`` frames excluded, so ledger
    totals reconcile with :func:`parse_device_trace`), aggregating per
    category: ``{"device_ms", "bytes_gb", "flops_g", "op_count"}``.
    ``flops_g`` sums the per-op ``flops`` annotation where the trace
    carries one (TPU op profiles; absent on CPU traces and on the v5e
    traces taken so far, in which case the ledger is byte/time-only and
    the roofline model runs memory-side only — the measured programs ARE
    memory-bound, docs/PERFORMANCE.md).

    ``rules=None`` classifies into the generic :data:`OP_CLASSES` via
    :func:`classify_op`; passing an ordered ``[(category, regex), ...]``
    list (e.g. :data:`STAGE_RULES`) attributes by ``long_name`` instead.
    Missing/empty trace dirs return an empty ledger, never raise.
    """
    ledger: dict[str, dict] = {}
    for ev in iter_device_ops(trace_dir):
        args = ev.get("args") or {}
        long_name = args.get("long_name", "")
        if rules is not None:
            cat = categorize_long_name(long_name, rules)
        else:
            cat = classify_op(ev.get("name", ""), long_name)
        entry = ledger.setdefault(cat, {
            "device_ms": 0.0, "bytes_gb": 0.0, "flops_g": 0.0,
            "op_count": 0,
        })
        entry["device_ms"] += float(ev.get("dur", 0.0)) / 1e3
        entry["bytes_gb"] += float(
            args.get("raw_bytes_accessed", 0) or 0
        ) / 2**30
        entry["flops_g"] += float(args.get("flops", 0) or 0) / 1e9
        entry["op_count"] += 1
    return ledger


def top_device_ops(trace_dir: str, k: int = 10,
                   by: str = "bytes") -> list[dict]:
    """Top-``k`` device ops aggregated by op name over
    :func:`iter_device_ops`, ranked ``by`` "bytes" (time as tiebreaker —
    the default) or "time" (bytes as tiebreaker).

    The offline run reporter (scripts/report_run.py) renders both
    rankings — "where did the bytes go" and "where did the time go";
    same selection rule as the bench proxy, so an op that moves the
    proxy total is findable by name here. The bytes ranking is the
    deterministic one (bytes are a program property); the time ranking
    reflects the traced run's actual schedule, noise included.
    """
    return _rank_ops(_aggregate_device_ops(trace_dir), k, by)


def _aggregate_device_ops(trace_dir: str) -> dict[str, dict]:
    """Per-op-name byte/time/count aggregation over ONE pass of
    :func:`iter_device_ops` (the gzipped trace read is the expensive
    part — callers wanting several rankings aggregate once)."""
    agg: dict[str, dict] = {}
    for ev in iter_device_ops(trace_dir):
        args = ev.get("args") or {}
        name = ev.get("name", "<unnamed>")
        entry = agg.setdefault(
            name, {"name": name, "bytes_gb": 0.0, "device_ms": 0.0,
                   "count": 0}
        )
        entry["bytes_gb"] += float(args.get("raw_bytes_accessed", 0) or 0)
        entry["device_ms"] += float(ev.get("dur", 0.0)) / 1e3
        entry["count"] += 1
    for entry in agg.values():
        entry["bytes_gb"] = entry["bytes_gb"] / 2**30
    return agg


def _rank_ops(agg: dict[str, dict], k: int, by: str) -> list[dict]:
    if by not in ("bytes", "time"):
        raise ValueError(f"by must be 'bytes' or 'time', got {by!r}")
    ranked = sorted(
        agg.values(),
        key=(
            (lambda e: (e["bytes_gb"], e["device_ms"])) if by == "bytes"
            else (lambda e: (e["device_ms"], e["bytes_gb"]))
        ),
        reverse=True,
    )
    return ranked[:k]


def device_op_report(trace_dir: str, k: int = 10) -> dict:
    """Everything the offline reporter needs from a trace dir in ONE
    gzip pass: ``{"totals", "by_bytes", "by_time"}`` — the
    :func:`parse_device_trace` totals plus both top-op rankings."""
    agg = _aggregate_device_ops(trace_dir)
    return {
        "totals": {
            "device_ms": sum(e["device_ms"] for e in agg.values()),
            "bytes_gb": sum(e["bytes_gb"] for e in agg.values()),
            "op_count": sum(e["count"] for e in agg.values()),
        },
        "by_bytes": _rank_ops(agg, k, "bytes"),
        "by_time": _rank_ops(agg, k, "time"),
    }


# The trace-viewer JSON holds at most this many events and drops the
# rest in silence (a four-chip flagship trace overflows it).
_EVENT_CAP = 1_000_000


def _session_events(trace_dir: str) -> list[dict]:
    """Events of the newest profiling session under ``trace_dir`` in the
    trace-viewer JSON's shape. Read from that JSON where it is whole; at
    the event cap, from the ``.xplane.pb`` (every event): the device
    planes' ``XLA Modules`` lines and the host plane's events that carry
    a ``cat`` (the program's own spans, below)."""
    paths = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                               "*.trace.json.gz")),
        key=os.path.getmtime,
    )
    if not paths:
        return []
    with gzip.open(paths[-1], "rt") as f:
        events = json.load(f).get("traceEvents", [])
    if len(events) < _EVENT_CAP:
        return events
    pbs = glob.glob(os.path.join(os.path.dirname(paths[-1]), "*.xplane.pb"))
    if not pbs:
        return events
    out = []
    for pid, plane in enumerate(
        jax.profiler.ProfileData.from_file(pbs[0]).planes
    ):
        device = plane.name.startswith("/device:")
        if not device and not plane.name.startswith("/host:CPU"):
            continue
        out.append({"ph": "M", "pid": pid, "name": "process_name",
                    "args": {"name": plane.name}})
        for tid, line in enumerate(plane.lines):
            if device and line.name != "XLA Modules":
                continue
            out.append({"ph": "M", "pid": pid, "tid": tid,
                        "name": "thread_name", "args": {"name": line.name}})
            for ev in line.events:
                args = {} if device else dict(ev.stats)
                if not device and "cat" not in args:
                    continue
                out.append({
                    "ph": "X", "pid": pid, "tid": tid, "name": ev.name,
                    "ts": ev.start_ns / 1e3, "dur": ev.duration_ns / 1e3,
                    "args": args,
                })
    return out


def attribute_idle_gaps(trace_dir: str) -> list[dict]:
    """Name the device's idle gaps by what the host was doing in them.

    A gap is the time between two successive executed programs on one
    chip (the ``XLA Modules`` lane of a ``/device:`` plane; the rule of
    ``benchmark/harness/trace.py``). Every timed boundary of the program
    is also a ``TraceAnnotation`` of the span's name with ``cat`` and
    ``round`` as metadata (telemetry/spans.py), so the capture holds the
    host spans on the device trace's own clock, in its ``/host:CPU``
    plane. Each gap is named by the innermost such span that covers its
    midpoint, ``"<none>"`` where none does.

    Returns rows ``{"span", "between", "count", "seconds"}``, one per
    (span, ``<program before>-><program after>``) pair summed over
    chips, longest first. Missing/empty trace dirs return ``[]``.
    """
    events = _session_events(trace_dir)
    procs, threads = {}, {}
    for ev in events:
        if ev.get("ph") != "M":
            continue
        if ev.get("name") == "process_name":
            procs[ev["pid"]] = ev["args"]["name"]
        elif ev.get("name") == "thread_name":
            threads[(ev["pid"], ev.get("tid"))] = ev["args"]["name"]
    modules: dict[int, list] = {}
    host_spans = []
    for ev in events:
        if ev.get("ph") != "X":
            continue
        plane = procs.get(ev.get("pid"), "")
        if plane.startswith("/device:"):
            if threads.get((ev["pid"], ev.get("tid"))) == "XLA Modules":
                modules.setdefault(ev["pid"], []).append(ev)
        elif plane.startswith("/host:") and "cat" in (ev.get("args") or {}):
            host_spans.append(
                (float(ev["ts"]), float(ev["ts"]) + float(ev["dur"]),
                 ev["name"])
            )
    rows: dict[tuple, dict] = {}
    for mods in modules.values():
        mods.sort(key=lambda e: e["ts"])
        for prev, nxt in zip(mods, mods[1:]):
            end = float(prev["ts"]) + float(prev["dur"])
            gap = float(nxt["ts"]) - end
            if gap <= 0:
                continue
            mid = end + gap / 2
            covering = [h for h in host_spans if h[0] <= mid < h[1]]
            # Spans nest, so the shortest that covers is the innermost.
            span = min(covering, key=lambda h: h[1] - h[0])[2] \
                if covering else "<none>"
            between = "->".join(
                re.sub(r"\(\d+\)$", "", m["name"]) for m in (prev, nxt)
            )
            row = rows.setdefault((span, between), {
                "span": span, "between": between, "count": 0,
                "seconds": 0.0,
            })
            row["count"] += 1
            row["seconds"] += gap / 1e6
    return sorted(rows.values(), key=lambda r: (-r["seconds"], r["span"]))


@contextlib.contextmanager
def profile_session(profile_dir: str | None):
    """Profile the enclosed block into ``profile_dir`` (no-op if None)."""
    if not profile_dir:
        yield
        return
    # Without the Python tracer (on by default): it records every Python
    # call — a million events while round 0 traces and lowers the round
    # program — and the trace-viewer JSON this module parses is capped at
    # 1,000,000 events, so device ops were dropped from it (v5e, PR 21:
    # 2,148 of the 6,576 captured ops survived a three-round trace).
    # The program's span annotations (telemetry/spans.py) and device ops
    # do not come from that tracer.
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(profile_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
