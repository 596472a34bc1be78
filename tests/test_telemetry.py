"""telemetry/ subsystem: recompile counter, phase timing, records, report.

Acceptance pins (ISSUE 3): a deliberately shape-unstable run trips the
recompilation counter with the offending function name surfaced in the
log; a stable run reports 0 post-warmup compiles; report_run renders a
real run's artifacts dir; telemetry_level='off' leaves metrics.jsonl
records in the legacy (v1) layout.
"""

import dataclasses
import glob
import json
import logging
import os

import jax
import jax.numpy as jnp
import pytest

from distributed_learning_simulator_tpu.telemetry import (
    NullPhaseTimer,
    PhaseTimer,
    RecompileMonitor,
    device_memory_stats,
    hbm_limit_bytes,
    log_round_compiles,
    make_phase_timer,
    peak_hbm_bytes,
)
from distributed_learning_simulator_tpu.utils.reporting import (
    METRICS_SCHEMA_VERSION,
    build_round_record,
    config_hash,
)

# ---------------------------------------------------------------- recompile


def test_recompile_monitor_shape_unstable_run():
    """A deliberately shape-unstable jitted function trips the counter —
    with its name — while the cached-shape call counts zero."""
    mon = RecompileMonitor()
    with mon:
        @jax.jit
        def wobbly_step(x):
            return x * 2.0

        wobbly_step(jnp.ones(8)).block_until_ready()
        mon.attribute(0)  # warmup: first shape compiles
        wobbly_step(jnp.ones(8)).block_until_ready()
        mon.attribute(1)  # cached: no compile
        wobbly_step(jnp.ones(9)).block_until_ready()  # NEW shape: recompile
        mon.attribute(2)
    warmup, stable, unstable = mon.take(0), mon.take(1), mon.take(2)
    assert any("wobbly_step" in name for name, _ in warmup)
    assert stable == []
    assert any("wobbly_step" in name for name, _ in unstable)
    # take() pops: a second read is empty.
    assert mon.take(2) == []


def test_recompile_monitor_restores_global_state():
    """start/stop must restore jax_log_compiles and the compile loggers'
    propagation — the monitor owns process-global state only while
    active."""
    dispatch = logging.getLogger("jax._src.dispatch")
    before_flag = bool(jax.config.jax_log_compiles)
    before_prop = dispatch.propagate
    before_handlers = list(dispatch.handlers)
    mon = RecompileMonitor().start()
    assert bool(jax.config.jax_log_compiles) is True
    assert dispatch.propagate is False
    mon.stop()
    assert bool(jax.config.jax_log_compiles) == before_flag
    assert dispatch.propagate == before_prop
    assert dispatch.handlers == before_handlers
    mon.stop()  # idempotent


def test_log_round_compiles_surfaces_offender_name():
    """Post-warmup compiles WARN with the offending function name; warmup
    compiles stay at INFO."""
    logger = logging.getLogger("test_telemetry_compiles")
    logger.propagate = True
    records = []

    class _Cap(logging.Handler):
        def emit(self, r):
            records.append(r)

    h = _Cap()
    logger.addHandler(h)
    logger.setLevel(logging.INFO)
    try:
        n = log_round_compiles(
            logger, 7, [("round_fn", 12.5)], warmup=False
        )
        assert n == 1
        warn = [r for r in records if r.levelno == logging.WARNING]
        assert len(warn) == 1
        msg = warn[0].getMessage()
        assert "round_fn" in msg and "round 7" in msg
        assert "AFTER warmup" in msg
        records.clear()
        log_round_compiles(logger, 0, [("round_fn", 12.5)], warmup=True)
        assert all(r.levelno == logging.INFO for r in records)
        assert log_round_compiles(logger, 3, [], warmup=False) == 0
    finally:
        logger.removeHandler(h)


# ------------------------------------------------------------- phase timer


def test_phase_timer_accumulates_and_pops():
    t = PhaseTimer(fence=False)
    with t.phase(0, "client_step"):
        pass
    with t.phase(0, "client_step"):  # same phase accumulates
        pass
    with t.phase(0, "eval"):
        pass
    with t.phase(1, "client_step"):
        pass
    r0 = t.take(0)
    assert set(r0) == {"client_step", "eval"}
    assert all(v >= 0.0 for v in r0.values())
    assert t.take(0) == {}  # popped
    assert set(t.take(1)) == {"client_step"}


def test_phase_timer_fences_on_device_value():
    """With fence=True the phase blocks on the parked output before the
    clock stops (block_until_ready on the fenced tree must not raise on
    nested containers)."""
    t = PhaseTimer(fence=True)
    with t.phase(0, "client_step") as ph:
        out = jax.jit(lambda x: x * 3.0)(jnp.ones((64, 64)))
        ph.fence((out, {"aux": out}))
    assert t.take(0)["client_step"] > 0.0


def test_make_phase_timer_levels():
    assert isinstance(make_phase_timer("off"), NullPhaseTimer)
    assert not make_phase_timer("off").enabled
    basic = make_phase_timer("basic")
    assert isinstance(basic, PhaseTimer) and not basic._fence
    assert make_phase_timer("detailed")._fence
    null = make_phase_timer("off")
    with null.phase(0, "x") as ph:
        ph.fence(jnp.ones(2))
    assert null.take(0) is None


# ------------------------------------------------------------ memory probe


def test_memory_probe_graceful_on_cpu():
    """CPU reports no memory stats: every helper must return None, never
    raise (the graceful-None contract the watermark/budget callers
    rely on)."""
    stats = device_memory_stats()
    if stats is None:  # CPU backend (the CI case)
        assert peak_hbm_bytes() is None
        assert hbm_limit_bytes() is None
    else:  # a real accelerator: values are positive ints when present
        for v in (peak_hbm_bytes(), hbm_limit_bytes()):
            assert v is None or (isinstance(v, int) and v > 0)


# ----------------------------------------------------------- record builder


def test_build_round_record_off_is_identity():
    """telemetry=None returns the base record UNTOUCHED — the
    byte-identical-at-'off' guarantee reduces to this plus the
    integration test below."""
    base = {"round": 3, "test_accuracy": 0.5, "round_seconds": 1.0}
    out = build_round_record(base, None)
    assert out is base  # not even a copy: nothing can have changed
    assert json.dumps(out) == json.dumps(base)


def test_build_round_record_v2_layout():
    """A telemetry-only record stays at the v2 stamp byte-for-byte —
    the v3 layout exists only when a client_stats sub-object is present
    (tests/test_client_stats.py, tests/test_metrics_schema.py)."""
    base = {"round": 3, "test_accuracy": 0.5}
    tel = {"phase_seconds": {"eval": 0.1}, "compiles": 0}
    out = build_round_record(base, tel)
    assert out is not base and "telemetry" not in base
    assert out["schema_version"] == 2
    assert out["telemetry"] == tel
    assert out["round"] == 3
    v3 = build_round_record(base, tel, {"n_clients": 4})
    assert v3["schema_version"] == 3
    assert v3["client_stats"] == {"n_clients": 4}
    v4 = build_round_record(base, tel, None, {"on_time": 4})
    assert v4["schema_version"] == 4
    assert v4["async"] == {"on_time": 4}
    v5 = build_round_record(base, tel, None, None, {"h2d_bytes": 8})
    # Lowest-version stamping: a stream-carrying record stays v5 even
    # though the CURRENT top version has moved on (v6 costmodel, v7
    # valuation — their own tests pin those stamps).
    assert v5["schema_version"] == 5 <= METRICS_SCHEMA_VERSION
    assert v5["stream"] == {"h2d_bytes": 8}


def test_config_hash_tracks_program_knobs_only(tiny_config):
    h = config_hash(tiny_config)
    assert len(h) == 12
    same = dataclasses.replace(
        tiny_config, round=99, log_level="DEBUG",
        checkpoint_dir="/tmp/x", profile_dir="/tmp/y",
    )
    assert config_hash(same) == h
    assert config_hash(
        dataclasses.replace(tiny_config, model_name="lenet5")
    ) != h
    assert config_hash(
        dataclasses.replace(tiny_config, failure_mode="dropout")
    ) != h
    # 'detailed' fences every phase (not a comparable cost point), so
    # telemetry_level is a program-defining knob for the hash.
    assert config_hash(
        dataclasses.replace(tiny_config, telemetry_level="detailed")
    ) != h


def test_config_validates_telemetry_level(tiny_config):
    dataclasses.replace(tiny_config, telemetry_level="detailed").validate()
    with pytest.raises(ValueError, match="telemetry_level"):
        dataclasses.replace(tiny_config, telemetry_level="verbose").validate()


# ------------------------------------------------------------- integration


def _run_with_artifacts(cfg):
    from distributed_learning_simulator_tpu.simulator import run_simulation

    result = run_simulation(cfg)
    metrics = glob.glob(
        os.path.join(cfg.log_root, "**", "metrics.jsonl"), recursive=True
    )
    assert len(metrics) == 1
    with open(metrics[0]) as f:
        records = [json.loads(line) for line in f]
    return result, records, os.path.dirname(metrics[0])


def test_simulator_telemetry_stable_run(tiny_config, tmp_path):
    """A shape-stable vmap run: warmup compiles land in the first round's
    record, every later round reports 0 compiles, phase timings cover the
    round loop's regions, and the result dict's post_warmup_compiles
    gate is 0."""
    cfg = dataclasses.replace(
        tiny_config, round=3, telemetry_level="basic",
        compilation_cache_dir=None, log_root=str(tmp_path / "log"),
    )
    result, records, artifacts = _run_with_artifacts(cfg)
    assert result["post_warmup_compiles"] == 0
    assert result["telemetry_level"] == "basic"
    assert len(records) == 3
    # client_stats off (the default): telemetry-only records keep v2.
    assert all(r["schema_version"] == 2 for r in records)
    warmup = records[0]["telemetry"]
    assert warmup["compiles"] > 0
    assert any("round_fn" in n for n in warmup["compiled"])
    for r in records[1:]:
        assert r["telemetry"]["compiles"] == 0
        assert "compiled" not in r["telemetry"]
    for r in records:
        phases = r["telemetry"]["phase_seconds"]
        assert {"client_step", "eval", "host_sync", "post_round"} <= set(
            phases
        )
        assert all(v >= 0.0 for v in phases.values())

    # Offline reporter over the real artifacts dir (acceptance pin).
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "report_run",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "report_run.py"),
    )
    report_run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report_run)
    summary = report_run.summarize_run(
        report_run.load_metrics(artifacts)
    )
    assert summary["rounds"] == 3
    assert summary["compiles"]["post_warmup"] == 0
    assert summary["compiles"]["warmup"] > 0
    assert summary["final_accuracy"] == records[-1]["test_accuracy"]
    assert set(summary["phases"]) >= {"client_step", "eval"}
    assert summary["rejected_rounds"]["count"] == 0
    rendered = "\n".join(report_run.render_summary(summary))
    assert "post-warmup recompiles: none" in rendered
    assert "client_step" in rendered and "accuracy" in rendered


def _top_level(rec):
    root = [s for s in rec.spans()
            if s["name"] == "run" and s["parent"] is None]
    assert len(root) == 1
    return root[0], [s for s in rec.spans() if s["parent"] == root[0]["id"]]


def test_simulator_spans_cover_the_call(tiny_config, tmp_path):
    """At 'basic' the ONE recorder lives from the first line of
    run_simulation to its return: run -> setup/* -> one `round` per
    iteration; the top-level spans cover the call; one host_sync a
    round; the jax.monitoring counters see the compiles of set-up (the
    op-by-op model init), before any round; last_run() reads it after
    the return."""
    import time

    from distributed_learning_simulator_tpu.simulator import run_simulation
    from distributed_learning_simulator_tpu.telemetry import spans

    cfg = dataclasses.replace(
        tiny_config, round=4, telemetry_level="basic",
        compilation_cache_dir=None, log_root=str(tmp_path / "log"),
        # A model the process has not initialised yet: its set-up compiles.
        model_name="mlp", model_args={"hidden": 24},
    )
    t0 = time.perf_counter()
    result = run_simulation(cfg)
    wall = time.perf_counter() - t0
    rec = spans.last_run()
    assert rec is not None and rec.recording
    assert result["span_summary"] is None  # span_trace is off
    root, top = _top_level(rec)
    assert root["dur"] == pytest.approx(wall, rel=0.02)
    names = [s["name"] for s in top]
    sections = [n for n in names if n.startswith("setup/")]
    assert sections[:3] == ["setup/entry", "setup/data", "setup/model_init"]
    assert {"setup/resume", "setup/build"} <= set(sections)
    assert [s["round"] for s in top if s["name"] == "round"] == [0, 1, 2, 3]
    assert names[-1] == "teardown"
    covered = spans.union_seconds(
        (s["t0"], s["t0"] + s["dur"]) for s in top
    )
    assert covered >= 0.95 * wall
    # The loop body: dispatch -> eval_dispatch under `round`; the fetch,
    # post_round and the record under `finalize` (pipelined: round r's
    # inside round r+1's iteration, under ITS round number).
    by_id = {s["id"]: s for s in rec.spans()}
    for s in rec.spans():
        if s["name"] in ("dispatch", "eval_dispatch"):
            assert by_id[s["parent"]]["name"] == "round"
            assert by_id[s["parent"]]["round"] == s["round"]
        if s["name"] in ("host_sync", "post_round", "record"):
            assert by_id[s["parent"]]["name"] == "finalize"
            assert by_id[s["parent"]]["round"] == s["round"]
    fin = [s for s in rec.spans() if s["name"] == "finalize"]
    assert [by_id[s["parent"]].get("round") for s in fin] == [1, 2, 3, None]
    counters = rec.counters()
    assert counters["rounds"] == 4 and counters["host_syncs"] == 4
    assert len(rec.round_stamps()) == 4
    # Compiles before the first round completed: model init among them.
    assert counters["compile_s"][0] > 0 and counters["trace_s"][0] > 0
    in_init = [
        t for key, t, _ in rec.duration_events()
        if key == "compile_s" and any(
            s["name"] == "setup/model_init"
            and s["t0"] <= t <= s["t0"] + s["dur"] for s in top
        )
    ]
    assert in_init, "no compile seen inside setup/model_init"
    assert counters["compile_s"][1] == 0.0  # and none after warm-up
    # phase_seconds come from the same spans, under the PHASE names.
    assert set(result["history"][-1]["telemetry"]["phase_seconds"]) == {
        "client_step", "eval", "host_sync", "post_round",
    }
    disp = [s for s in rec.spans()
            if s["name"] == "dispatch" and s["round"] == 3][0]
    assert result["history"][-1]["telemetry"]["phase_seconds"][
        "client_step"] == round(disp["dur"], 6)


def test_one_monitoring_listener_from_the_first_line(tiny_config, tmp_path,
                                                     monkeypatch):
    """The run has ONE ``jax.monitoring`` duration listener, the
    RecompileMonitor's: switched on by the tracer at the first line of
    run_simulation (the recorder's trace/lower/compile counters come
    through it), counting for the records only from the round loop on,
    and unregistered by the return."""
    import jax
    from jax._src import monitoring

    from distributed_learning_simulator_tpu.simulator import run_simulation
    from distributed_learning_simulator_tpu.telemetry import spans

    registered = []
    real = jax.monitoring.register_event_duration_secs_listener
    monkeypatch.setattr(
        jax.monitoring, "register_event_duration_secs_listener",
        lambda fn: (registered.append(fn), real(fn))[1],
    )
    before = list(monitoring.get_event_duration_listeners())
    cfg = dataclasses.replace(
        tiny_config, round=2, telemetry_level="basic",
        compilation_cache_dir=None, log_root=str(tmp_path / "log"),
        model_name="mlp", model_args={"hidden": 20},  # set-up compiles
    )
    result = run_simulation(cfg)
    rec = spans.last_run()
    assert registered == [rec.monitor._on_duration]
    assert list(monitoring.get_event_duration_listeners()) == before
    # Heard by the recorder from the model init on ...
    compiles = [t for key, t, _ in rec.duration_events()
                if key == "compile_s"]
    init_ends = max(s["t0"] + s["dur"] for s in rec.spans()
                    if s["name"] == "setup/model_init")
    assert [t for t in compiles if t <= init_ends]
    # ... and not counted into the records: round 0 holds the loop's
    # warm-up compiles alone.
    warm = result["history"][0]["telemetry"]["compiles"]
    assert 0 < warm < len(compiles)
    assert result["post_warmup_compiles"] == 0


def test_records_do_not_change_with_the_recorder(tiny_config, tmp_path):
    """'off' and 'basic' train the same model and write the same record
    apart from the v2 telemetry sub-object; the recorder adds no key
    ('spans' is span_trace's), and at 'off' there is no recorder."""
    from distributed_learning_simulator_tpu.telemetry import spans

    runs = {}
    for level in ("off", "basic"):
        cfg = dataclasses.replace(
            tiny_config, round=3, telemetry_level=level,
            compilation_cache_dir=None,
            log_root=str(tmp_path / level),
        )
        result, records, _ = _run_with_artifacts(cfg)
        assert [json.dumps(r) for r in records] == [
            json.dumps(r) for r in result["history"]
        ]
        runs[level] = records
        assert (spans.last_run() is None) == (level == "off")
    for off, basic in zip(runs["off"], runs["basic"]):
        assert list(off) == ["round", "test_accuracy", "test_loss",
                             "mean_client_loss", "round_seconds"]
        assert list(basic) == list(off) + ["schema_version", "telemetry"]
        assert basic["schema_version"] == 2
        for key in ("round", "test_accuracy", "test_loss",
                    "mean_client_loss"):
            assert off[key] == basic[key]
        assert set(basic["telemetry"]) <= {
            "phase_seconds", "compiles", "compiled", "peak_hbm_bytes",
        }


def test_span_trace_journals_the_same_spans(tiny_config, tmp_path):
    """span_trace='on' adds the journal (and the v12 `spans` sub-object)
    to the SAME recorder: set-up sections, parents and rounds are in the
    file; the root span `run` is its last span line."""
    cfg = dataclasses.replace(
        tiny_config, round=2, telemetry_level="basic", span_trace="on",
        compilation_cache_dir=None, log_root=str(tmp_path / "log"),
        checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=1,
    )
    result, records, artifacts = _run_with_artifacts(cfg)
    assert all(r["schema_version"] == 12 and "spans" in r for r in records)
    # The v12 sub-object sums the leaf categories alone: the in-memory
    # envelopes (the iteration's `round`, `record`, `checkpoint`, set-up)
    # are journaled but not summed, so span_trace's numbers keep their
    # meaning.
    for r in records:
        assert set(r["spans"]["seconds_by_cat"]) <= {"phase", "round"}
    assert set(result["span_summary"]["seconds_by_cat"]) <= {
        "phase", "round", "compile",
    }
    path = result["span_summary"]["journal_path"]
    assert os.path.dirname(path) == artifacts
    lines = [json.loads(line) for line in open(path)]
    spans_ = [l for l in lines if l["kind"] == "span"]
    names = [l["name"] for l in spans_]
    assert names[-1] == "run" and spans_[-1]["parent"] is None
    assert "setup/model_init" in names and "teardown" in names
    assert names.count("round") == 2 and names.count("host_sync") == 2
    assert names.count("checkpoint") == 2
    cats = {l["name"]: l["cat"] for l in spans_}
    assert (cats["round"], cats["checkpoint"], cats["record"],
            cats["finalize"]) == ("iter", "host", "host", "round")
    finalize = sum(l["dur"] for l in spans_ if l["name"] == "finalize")
    assert result["span_summary"]["seconds_by_cat"]["round"] == (
        pytest.approx(finalize, abs=1e-5)
    )
    opens = [l["name"] for l in lines if l["kind"] == "open"]
    assert opens == ["finalize", "finalize"]  # the eager envelope
    assert not [l for l in lines if l.get("name") == "dispatch"
                and l["kind"] == "event"]  # the old instant mark is gone


@pytest.mark.parametrize("batch_size,unrolled", [(64, 2), (32, 0)])
def test_local_steps_unrolled_counter(tiny_config, tmp_path, batch_size,
                                      unrolled):
    """The recorder says whether the round program holds the local steps
    unrolled (parallel/engine.UNROLL_MAX_LOCAL_STEPS): 128-sample shards
    at batch 64 are 2 steps, unrolled; at batch 32 they are 4 and stay a
    loop (0). The count is in ``counters()``, in the journal as a
    ``counter`` event, and on the report's host line."""
    import importlib.util

    from distributed_learning_simulator_tpu.telemetry import spans

    cfg = dataclasses.replace(
        tiny_config, round=1, batch_size=batch_size,
        telemetry_level="basic", span_trace="on",
        compilation_cache_dir=None, log_root=str(tmp_path / "log"),
    )
    result, _records, _artifacts = _run_with_artifacts(cfg)
    assert spans.last_run().counters()["local_steps_unrolled"] == unrolled
    path = result["span_summary"]["journal_path"]
    events = [json.loads(line) for line in open(path)]
    events = [e for e in events if e.get("cat") == "counter"]
    assert [(e["kind"], e["name"], e["attrs"]["value"]) for e in events] == [
        ("event", "local_steps_unrolled", unrolled),
        ("event", "client_axis_width", 4),  # the 4 clients in one chunk
        ("event", "head_backward_tied", 0),  # this model hands on logits
        ("event", "attention_window", 0),  # no layer is windowed
        ("event", "swa_keys_per_query_block", 0),
        ("event", "fused_attention_layers", 0),  # no attention, and a CPU
        ("event", "global_donated", 0),  # a pipelined loop keeps the global
    ]
    spec = importlib.util.spec_from_file_location(
        "trace_timeline",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "trace_timeline.py"),
    )
    tt = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tt)
    rendered = tt.render_text(tt.summarize([tt.load_journal(path)]))
    assert f"local_steps_unrolled: {unrolled}" in rendered
    assert "head_backward_tied: 0" in rendered


def test_run_simulation_keeps_its_frame():
    """``run_simulation`` is one function whose frame lies above the first
    round's lowering: its slots (names + stack) decide where CPython's
    data stack crosses a chunk there, and ``setup_s`` moves by seconds
    with a few of them (PERF.md § 6, the data-stack cliff). 164 + 23 since
    PR 31; change the numbers only with a chip run that compares
    ``setup_trace_lower_s`` against the parent's."""
    from distributed_learning_simulator_tpu.simulator import run_simulation

    code = run_simulation.__code__
    names = set(code.co_varnames) | set(code.co_cellvars) | set(
        code.co_freevars)
    assert (len(names), code.co_stacksize) == (164, 23)


def test_simulator_telemetry_off_keeps_v1_records(tiny_config, tmp_path):
    """telemetry_level='off' (the default) emits the legacy v1 record —
    exactly the pre-telemetry key set, no schema_version, no telemetry
    sub-object."""
    cfg = dataclasses.replace(
        tiny_config, round=2, log_root=str(tmp_path / "log"),
    )
    assert cfg.telemetry_level == "off"
    result, records, _ = _run_with_artifacts(cfg)
    assert result["post_warmup_compiles"] is None
    for r in records:
        assert set(r) == {
            "round", "test_accuracy", "test_loss", "mean_client_loss",
            "round_seconds",
        }


def test_threaded_telemetry_basic(tmp_path):
    """The threaded oracle reports through the same builder: schema-v2
    records with server-side phase timings, and a run-level compile
    count in the result dict."""
    from distributed_learning_simulator_tpu.config import ExperimentConfig
    from distributed_learning_simulator_tpu.simulator import run_simulation

    cfg = ExperimentConfig(
        dataset_name="synthetic", model_name="mlp",
        distributed_algorithm="fed", worker_number=2, round=2, epoch=1,
        learning_rate=0.1, batch_size=32, n_train=128, n_test=64,
        log_level="WARNING", dataset_args={"difficulty": 0.5},
        execution_mode="threaded", telemetry_level="basic",
        compilation_cache_dir=None, log_root=str(tmp_path / "log"),
    )
    result = run_simulation(cfg)
    assert result["xla_compiles"] > 0
    assert result["telemetry_level"] == "basic"
    metrics = glob.glob(
        os.path.join(cfg.log_root, "**", "metrics.jsonl"), recursive=True
    )
    with open(metrics[0]) as f:
        records = [json.loads(line) for line in f]
    assert len(records) == 2
    for r in records:
        assert r["schema_version"] == 2
        assert {"aggregate", "eval", "post_round"} <= set(
            r["telemetry"]["phase_seconds"]
        )
