"""utils/tracing.py: device-trace parsing against synthetic fixtures.

iter_device_ops / parse_device_trace define the event-selection rule the
bench regression proxy depends on (complete 'X' events with XLA op
annotations, wrapper ``while``/``jit(`` frames excluded). These tests pin
that rule with hand-built gzipped ``*.trace.json.gz`` fixtures, so a
selection-rule regression shows up here instead of as a silently shifted
proxy baseline.
"""

import gzip
import json
import os

import pytest

from distributed_learning_simulator_tpu.utils.tracing import (
    OP_CLASSES,
    STAGE_RULES,
    attribute_idle_gaps,
    categorize_long_name,
    categorize_ops,
    classify_op,
    device_op_report,
    iter_device_ops,
    parse_device_trace,
    top_device_ops,
)

GIB = 2**30


def _write_trace(root, events, run="run1", fname="host.trace.json.gz"):
    """Lay out the jax.profiler directory shape the parser globs:
    ``<root>/plugins/profile/<run>/<fname>``."""
    d = os.path.join(root, "plugins", "profile", run)
    os.makedirs(d, exist_ok=True)
    with gzip.open(os.path.join(d, fname), "wt") as f:
        json.dump({"traceEvents": events}, f)


def _op(name, dur_us, nbytes=None, long_name=None):
    args = {}
    if nbytes is not None:
        args["raw_bytes_accessed"] = nbytes
    if long_name is not None:
        args["long_name"] = long_name
    return {"ph": "X", "name": name, "dur": dur_us, "args": args}


def test_selection_rule_and_aggregation(tmp_path):
    """Annotated X events are summed; wrapper frames, non-X phases, and
    unannotated host events are excluded even when they carry bytes."""
    events = [
        _op("fusion.1", 100.0, nbytes=GIB, long_name="fusion kernel"),
        _op("copy.2", 50.0, nbytes=GIB // 2),
        # Wrapper frames: would double count their children's bytes/time.
        _op("while", 1000.0, nbytes=100 * GIB),
        _op("jit(round_fn)", 800.0, nbytes=100 * GIB, long_name="jit frame"),
        # Non-X phase events are skipped outright.
        {"ph": "M", "name": "process_name", "args": {"name": "meta"}},
        # X event with no op annotation (host lane) is skipped.
        {"ph": "X", "name": "host_callback", "dur": 5.0},
        # long_name alone qualifies (CPU traces carry no byte counts).
        _op("dot.3", 25.0, long_name="dot_general"),
    ]
    _write_trace(str(tmp_path), events)
    ops = list(iter_device_ops(str(tmp_path)))
    assert sorted(ev["name"] for ev in ops) == [
        "copy.2", "dot.3", "fusion.1",
    ]
    stats = parse_device_trace(str(tmp_path))
    assert stats["op_count"] == 3
    assert stats["device_ms"] == (100.0 + 50.0 + 25.0) / 1e3
    assert stats["bytes_gb"] == (GIB + GIB // 2) / GIB


def test_wrapper_exclusion_is_prefix_based(tmp_path):
    """The exclusion rule is the documented name-PREFIX match: any
    ``while*``/``jit(*`` name is a wrapper, whatever its suffix."""
    events = [
        _op("while.body.fusion", 10.0, nbytes=GIB),  # prefix 'while' -> out
        _op("jit(train_step)/mul", 10.0, nbytes=GIB),  # prefix 'jit(' -> out
        _op("jitted_mul", 10.0, nbytes=GIB),  # 'jit' but not 'jit(' -> in
    ]
    _write_trace(str(tmp_path), events)
    names = [ev["name"] for ev in iter_device_ops(str(tmp_path))]
    assert names == ["jitted_mul"]


def test_missing_and_empty_dirs_yield_nothing(tmp_path):
    """Missing/empty trace dirs parse to zeros, never raise (bench's
    proxy leg must degrade, not crash, when a trace comes back empty)."""
    missing = str(tmp_path / "nope")
    assert list(iter_device_ops(missing)) == []
    assert parse_device_trace(missing) == {
        "device_ms": 0.0, "bytes_gb": 0.0, "op_count": 0,
    }
    empty = tmp_path / "empty"
    empty.mkdir()
    assert parse_device_trace(str(empty)) == {
        "device_ms": 0.0, "bytes_gb": 0.0, "op_count": 0,
    }
    # A session dir whose trace holds no events at all.
    _write_trace(str(tmp_path / "blank"), [])
    assert parse_device_trace(str(tmp_path / "blank"))["op_count"] == 0


def test_multiple_trace_files_are_summed(tmp_path):
    """Every *.trace.json.gz under the dir contributes (the documented
    one-session-per-dir contract: a reused dir accumulates)."""
    _write_trace(str(tmp_path), [_op("a", 10.0, nbytes=GIB)],
                 fname="one.trace.json.gz")
    _write_trace(str(tmp_path), [_op("b", 20.0, nbytes=GIB)],
                 fname="two.trace.json.gz")
    stats = parse_device_trace(str(tmp_path))
    assert stats["op_count"] == 2
    assert stats["bytes_gb"] == 2.0


def test_classify_op_classes():
    """The op-class rules the cost model prices by: collectives before
    matmul (an all-reduce OF conv grads is ICI volume), copies by name
    PREFIX only, the u8 shard decode as its own byte budget."""
    assert classify_op("all-reduce.1") == "collective"
    assert classify_op("reduce-scatter.2") == "collective"
    assert classify_op("convolution.5", "convolution") == "matmul_conv"
    assert classify_op("convolution_convert_fusion.3") == "matmul_conv"
    assert classify_op("dot.3", "dot_general") == "matmul_conv"
    assert classify_op("fusion.8", "... dot_general ...") == "matmul_conv"
    assert classify_op("copy.2") == "copy_layout"
    assert classify_op("transpose.1") == "copy_layout"
    assert classify_op("bitcast.9") == "copy_layout"
    # A fusion whose long_name merely mentions copy is NOT a copy.
    assert classify_op("fusion.4", "copies nothing") == "elementwise"
    assert classify_op("fusion.9", "u8[1000,50,3072]") == "decode"
    # s32 alone is NOT decode: eval argmax / cohort-index fusions keep
    # their own class (only the stage map treats s32 as decode).
    assert classify_op("fusion.10", "s32[1000] argmax") == "elementwise"
    assert classify_op("dot.4", "dot_general s32[40] indices") == \
        "matmul_conv"
    assert classify_op("loop_reduce_fusion.2") == "elementwise"
    assert classify_op("convert.1") == "elementwise"
    assert classify_op("dynamic-update-slice.1") == "other"
    for name in ("all-reduce.1", "fusion.1", "copy.1", "custom-call.2"):
        assert classify_op(name) in OP_CLASSES


def test_categorize_long_name_stage_rules():
    """The promoted scripts/trace_categories.py rule table: first match
    wins, unmatched long_names land in 'other'."""
    assert categorize_long_name("= f32[3,3,256,256]") == "s3_wgrad"
    assert categorize_long_name("fusion over 8,8,256 tensors") == "stage3"
    assert categorize_long_name("u8[1000,50,3072] decode") == "decode"
    assert categorize_long_name("nothing recognizable") == "other"
    assert [c for c, _ in STAGE_RULES][:4] == [
        "s4_wgrad", "s3_wgrad", "s2_wgrad", "s1_wgrad",
    ]


def test_categorize_ops_ledger(tmp_path):
    """categorize_ops shares iter_device_ops' selection rule (wrapper
    frames excluded) and aggregates bytes/time/flops/count per class;
    ledger totals reconcile with parse_device_trace."""
    events = [
        _op("convolution.1", 100.0, nbytes=GIB, long_name="convolution"),
        _op("fusion.2", 50.0, nbytes=GIB // 2, long_name="loop fusion"),
        _op("fusion.2", 25.0, nbytes=GIB // 2, long_name="loop fusion"),
        _op("copy.3", 10.0, nbytes=GIB // 4),
        _op("all-reduce.4", 5.0, nbytes=GIB // 4),
        # Wrapper frames and unannotated host events stay excluded.
        _op("while", 1000.0, nbytes=100 * GIB),
        {"ph": "X", "name": "host_callback", "dur": 5.0},
    ]
    # One event carrying an XLA flops annotation.
    events[0]["args"]["flops"] = 4e9
    _write_trace(str(tmp_path), events)
    ledger = categorize_ops(str(tmp_path))
    assert set(ledger) == {"matmul_conv", "elementwise", "copy_layout",
                           "collective"}
    assert ledger["matmul_conv"] == {
        "device_ms": pytest.approx(0.1), "bytes_gb": 1.0,
        "flops_g": pytest.approx(4.0), "op_count": 1,
    }
    assert ledger["elementwise"]["op_count"] == 2
    assert ledger["elementwise"]["bytes_gb"] == 1.0
    totals = parse_device_trace(str(tmp_path))
    assert sum(e["bytes_gb"] for e in ledger.values()) == pytest.approx(
        totals["bytes_gb"]
    )
    assert sum(e["op_count"] for e in ledger.values()) == (
        totals["op_count"]
    )
    # Stage-rule mode: the same pass keyed by long_name rules.
    staged = categorize_ops(str(tmp_path), rules=STAGE_RULES)
    assert set(staged) == {"other"}  # no flagship shapes in this fixture
    assert staged["other"]["op_count"] == 5
    # Missing dirs yield an empty ledger, never raise.
    assert categorize_ops(str(tmp_path / "missing")) == {}


def test_top_device_ops_ranks_by_bytes(tmp_path):
    """top_device_ops aggregates per op name and ranks by bytes with time
    as tiebreaker — the report_run 'where did the bytes go' table."""
    events = [
        _op("fusion.1", 10.0, nbytes=GIB),
        _op("fusion.1", 10.0, nbytes=GIB),      # same name: aggregated
        _op("copy.2", 500.0, nbytes=GIB // 4),  # slow but few bytes
        _op("zerobytes.a", 90.0, long_name="x"),   # 0 B, more time
        _op("zerobytes.b", 10.0, long_name="y"),   # 0 B, less time
    ]
    _write_trace(str(tmp_path), events)
    top = top_device_ops(str(tmp_path), k=10)
    assert [t["name"] for t in top] == [
        "fusion.1", "copy.2", "zerobytes.a", "zerobytes.b",
    ]
    assert top[0]["count"] == 2 and top[0]["bytes_gb"] == 2.0
    assert top_device_ops(str(tmp_path), k=1)[0]["name"] == "fusion.1"
    assert top_device_ops(str(tmp_path / "missing")) == []

    # by="time": same aggregation, ranked on device time with bytes as
    # tiebreaker — report_run's "where did the time go" table.
    by_time = top_device_ops(str(tmp_path), k=10, by="time")
    assert [t["name"] for t in by_time] == [
        "copy.2", "zerobytes.a", "fusion.1", "zerobytes.b",
    ]
    with pytest.raises(ValueError, match="by"):
        top_device_ops(str(tmp_path), by="flops")

    # device_op_report: totals + both rankings from ONE gzip pass must
    # match the single-purpose helpers (report_run consumes this).
    report = device_op_report(str(tmp_path), k=10)
    assert report["by_bytes"] == top
    assert report["by_time"] == by_time
    single = parse_device_trace(str(tmp_path))
    assert report["totals"]["op_count"] == single["op_count"]
    assert report["totals"]["bytes_gb"] == pytest.approx(single["bytes_gb"])
    assert report["totals"]["device_ms"] == pytest.approx(
        single["device_ms"]
    )


# ----------------------------------------------------- idle-gap attribution


def _meta(pid, name, tid=None, thread=None):
    if tid is None:
        return {"ph": "M", "pid": pid, "name": "process_name",
                "args": {"name": name}}
    return {"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
            "args": {"name": thread}}


def _x(pid, tid, name, ts, dur, **args):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
            "dur": dur, "args": args}


def test_attribute_idle_gaps_names_gaps_by_host_spans(tmp_path):
    """A hand-made capture with a host plane: two chips run round_fn then
    server_eval twice; the host's spans (TraceAnnotations carrying
    ``cat``) lie on the same clock. Each gap between programs is named by
    the INNERMOST span over its midpoint; runtime events without ``cat``
    never name one; a gap no span covers reads ``<none>``."""
    events = [
        _meta(1, "/device:TPU:0"), _meta(1, None, 2, "XLA Modules"),
        _meta(1, None, 3, "XLA Ops"),
        _meta(2, "/device:TPU:1"), _meta(2, None, 2, "XLA Modules"),
        _meta(9, "/host:CPU"), _meta(9, None, 7, "python"),
    ]
    for pid in (1, 2):
        events += [
            _x(pid, 2, "jit_round_fn(123)", 1000.0, 600.0),
            # gap 1600-1610 (10 us): inside `eval_dispatch`
            _x(pid, 2, "jit_server_eval(456)", 1610.0, 90.0),
            # gap 1700-2000 (300 us): inside `dispatch` of the next round
            _x(pid, 2, "jit_round_fn(123)", 2000.0, 600.0),
            # gap 2600-2640 (40 us): no span of the program covers it
            _x(pid, 2, "jit_server_eval(456)", 2640.0, 90.0),
        ]
    # An op lane event between the programs must not split the gap.
    events.append(_x(1, 3, "fusion.1", 1601.0, 2.0, long_name="fusion"))
    events += [
        _x(9, 7, "round", 900.0, 850.0, cat="round", round="0"),
        _x(9, 7, "eval_dispatch", 1590.0, 30.0, cat="phase", round="0"),
        _x(9, 7, "round", 1750.0, 800.0, cat="round", round="1"),
        _x(9, 7, "dispatch", 1800.0, 150.0, cat="phase", round="1"),
        # A runtime TraceMe without `cat` covering everything: ignored.
        _x(9, 7, "PjitFunction(round_fn)", 0.0, 5000.0),
    ]
    _write_trace(str(tmp_path), events)
    rows = attribute_idle_gaps(str(tmp_path))
    assert rows == [
        {"span": "dispatch", "count": 2, "seconds": pytest.approx(600e-6),
         "between": "jit_server_eval->jit_round_fn"},
        {"span": "<none>", "count": 2, "seconds": pytest.approx(80e-6),
         "between": "jit_round_fn->jit_server_eval"},
        {"span": "eval_dispatch", "count": 2,
         "seconds": pytest.approx(20e-6),
         "between": "jit_round_fn->jit_server_eval"},
    ]
    assert attribute_idle_gaps(str(tmp_path / "missing")) == []


def test_report_run_renders_the_gap_attribution():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "report_run",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "report_run.py"),
    )
    report_run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report_run)
    summary = report_run.summarize_run(
        [{"round": 0, "test_accuracy": 0.5, "round_seconds": 1.0}],
        idle_gaps=[{"span": "dispatch", "count": 2, "seconds": 0.0037,
                    "between": "jit_server_eval->jit_round_fn"}],
    )
    assert summary["device_idle_gaps"][0]["span"] == "dispatch"
    text = "\n".join(report_run.render_summary(summary))
    assert "device idle gaps by host span:" in text
    assert "3.700 ms" in text and "jit_server_eval->jit_round_fn" in text


def test_session_events_fall_back_to_the_xplane_at_the_event_cap(
        tmp_path, monkeypatch):
    """The trace-viewer JSON drops events beyond its cap in silence, so a
    capture that reaches it is read from the ``.xplane.pb``: the host
    plane's spans arrive with their ``cat``/``round`` metadata, runtime
    events without ``cat`` are left out."""
    import jax.numpy as jnp

    from distributed_learning_simulator_tpu.telemetry.spans import NullTracer
    from distributed_learning_simulator_tpu.utils import tracing

    tracer = NullTracer()
    with tracing.profile_session(str(tmp_path)):
        with tracer.span("round", "round", round_idx=7):
            with tracer.span("dispatch", "phase", round_idx=7):
                jnp.ones(8).sum().block_until_ready()
    from_json = [e for e in tracing._session_events(str(tmp_path))
                 if "cat" in (e.get("args") or {})]
    monkeypatch.setattr(tracing, "_EVENT_CAP", 1)
    events = tracing._session_events(str(tmp_path))
    spans = [e for e in events if e["ph"] == "X"]
    assert sorted(e["name"] for e in spans) == ["dispatch", "round"]
    assert {e["args"]["cat"] for e in spans} == {"phase", "round"}
    assert {int(e["args"]["round"]) for e in spans} == {7}
    planes = [e["args"]["name"] for e in events
              if e["ph"] == "M" and e["name"] == "process_name"]
    assert planes == ["/host:CPU"]
    # Same spans, same clock, by either route.
    assert sorted((e["name"], round(e["ts"], 1)) for e in from_json) == (
        sorted((e["name"], round(e["ts"], 1)) for e in spans)
    )
    # No device plane in a CPU capture: nothing to attribute, no error.
    assert tracing.attribute_idle_gaps(str(tmp_path)) == []
