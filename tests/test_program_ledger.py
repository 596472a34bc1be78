"""The ledger of programs and the set-up spans of resume and save.

* ``recompile.program_name``, the recorder's ``DurationEvent`` and
  ``programs()``;
* the cache's outcome on a real persistent cache in ``tmp_path``, with
  the benchmark's two thresholds: a first compile is a ``miss``, the same
  function after ``jax.clear_caches()`` a ``hit``, and a function traced
  inside another's trace is a program of its own whose interval nests;
* a tiny ``run_simulation`` that checkpoints, resumes and is forced to
  recompile: ``resume/read`` / ``resume/place`` under ``setup/resume``,
  ``save/fetch`` / ``save/write`` under ``checkpoint``, and the records'
  ``compiled`` names are the names ``jax_log_compiles`` prints, which is
  where the monitor used to take them from;
* the benchmark's five readers (``benchmark/metrics/<name>.py``, loaded
  by path as ``run.py`` loads them) over a recorder fed by hand: every
  number below is arithmetic on hand-made events and spans.
"""

import dataclasses
import importlib.util
import logging
import os
import re
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from distributed_learning_simulator_tpu.telemetry import clock, spans
from distributed_learning_simulator_tpu.telemetry.recompile import (
    CACHE_EVENTS,
    DURATION_EVENTS,
    program_name,
)
from distributed_learning_simulator_tpu.telemetry.spans import (
    DurationEvent,
    SpanRecorder,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
FIVE = (
    "setup_round_program_trace_lower_s", "setup_compile_cold_s",
    "setup_resume_read_s", "setup_resume_place_s", "setup_save_s",
)
TRACE, LOWER, COMPILE = DURATION_EVENTS
HIT, MISS = CACHE_EVENTS


@pytest.mark.parametrize("name,program", [
    ("round_fn", "round_fn"),         # a trace event
    ("jit(round_fn)", "round_fn"),    # a lowering or compile event
    ("jit_round_fn", "round_fn"),     # the HLO module a profile names
    ("jit(<lambda>)", "<lambda>"),
    ("jit(jit_step)", "step"),        # one name for all three forms
    ("pmap(step)", "pmap(step)"),
])
def test_program_name_is_one_name(name, program):
    assert program_name(name) == program


def test_duration_event_unpacks_as_the_three_it_was():
    ev = DurationEvent("compile_s", 5.0, 2.0, "round_fn", "miss")
    counter, t_end, seconds = ev
    assert (counter, t_end, seconds) == ("compile_s", 5.0, 2.0)
    assert (ev.program, ev.cache) == ("round_fn", "miss")


def _at(t, fn, *args, **kwargs):
    """Call ``fn`` with the recorder's clock reading ``t``."""
    real = clock.monotonic
    clock.monotonic = lambda: t
    try:
        return fn(*args, **kwargs)
    finally:
        clock.monotonic = real


def test_programs_split_by_program_half_and_outcome():
    """The monitor hands the recorder each event's program and, on a
    compile, the mark the cache's event left on that thread; the
    ledger keeps them by program, before/after the first round."""
    rec = SpanRecorder()
    mon = rec.monitor
    _at(2.0, mon._on_duration, TRACE, 2.0, fun_name="round_fn")
    _at(1.5, mon._on_duration, TRACE, 0.5, fun_name="inner")  # nested
    _at(3.0, mon._on_duration, LOWER, 1.0, fun_name="jit(round_fn)")
    mon._on_cache(MISS)
    _at(5.0, mon._on_duration, COMPILE, 2.0, fun_name="jit(round_fn)")
    _at(5.5, mon._on_duration, COMPILE, 0.5, fun_name="jit(add)")
    mon._on_cache("/jax/compilation_cache/compile_requests_use_cache")
    rec.round_done(0, 6.0)
    mon._on_cache(HIT)
    _at(7.0, mon._on_duration, COMPILE, 0.25, fun_name="jit(round_fn)")
    got = rec.programs()
    assert set(got) == {"round_fn", "inner", "add"}
    rf = got["round_fn"]
    assert rf["trace_s"] == [[(0.0, 2.0)], []]
    assert rf["lower_s"] == [[(2.0, 3.0)], []]
    assert rf["compile_s"] == [[(3.0, 5.0)], [(6.75, 7.0)]]
    assert rf["cache"] == {"hit": [[], [(6.75, 7.0)]],
                           "miss": [[(3.0, 5.0)], []],
                           "uncached": [[], []]}
    assert got["inner"]["trace_s"] == [[(1.0, 1.5)], []]
    assert got["add"]["cache"]["uncached"] == [[(5.0, 5.5)], []]
    assert [(e.program, e.cache) for e in rec.duration_events()] == [
        ("round_fn", None), ("inner", None), ("round_fn", None),
        ("round_fn", "miss"), ("add", "uncached"), ("round_fn", "hit"),
    ]
    # The counters are the same unions as before the ledger.
    assert rec.counters()["compile_s"] == [2.5, 0.25]


def test_the_ring_of_durations_says_what_it_evicted():
    rec = SpanRecorder(capacity=2)
    rec.round_done(0, 1.0)
    for t in (2.0, 3.0, 4.0):
        _at(t, rec.monitor._on_duration, COMPILE, 0.1, fun_name="f")
    assert rec.evicted_until == 2.0
    assert [e.t_end for e in rec.duration_events()] == [3.0, 4.0]


@pytest.fixture
def fresh_cache(tmp_path):
    """jax's persistent cache in ``tmp_path`` with the benchmark's two
    thresholds (every program cached), set and restored here; no other
    test's cache leaks in or out."""
    from jax._src import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    compilation_cache.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        yield
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()


def test_cache_outcomes_on_a_persistent_cache(fresh_cache):
    def inner(x):
        return jnp.sin(x) * 2.0

    def outer(x):
        return jax.jit(inner)(x) + 1.0

    x = jnp.arange(4.0)
    rec = SpanRecorder()
    rec.monitor.start()
    try:
        f = jax.jit(outer)
        f(x).block_until_ready()
        jax.clear_caches()
        f(x).block_until_ready()
    finally:
        rec.monitor.stop()
    got = rec.programs()["outer"]
    (miss,), (hit,) = got["cache"]["miss"][0], got["cache"]["hit"][0]
    assert miss[1] <= hit[0]  # compiled and written, then loaded
    assert got["compile_s"][0] == [miss, hit]
    assert got["cache"]["uncached"] == [[], []]
    # `inner` is traced inside `outer`'s trace, each time: a program of
    # its own, lowered and compiled as part of `outer`.
    ins = rec.programs()["inner"]
    assert len(ins["trace_s"][0]) == 2 and ins["compile_s"] == [[], []]
    for a, b in ins["trace_s"][0]:
        assert any(o0 <= a and b <= o1 for o0, o1 in got["trace_s"][0])
    assert [n for n, _ in rec.monitor.drain()] == ["outer", "outer"]


# The route the monitor took names by before it read them from the
# events: ``jax_log_compiles`` on, and this pattern over the lines.
_FINISHED_RE = re.compile(
    r"Finished XLA compilation of (?:jit\()?([^)\s]+)\)? in ([0-9.eE+-]+) sec"
)


def test_resume_and_save_spans_and_the_compiled_names(tiny_config, tmp_path,
                                                       monkeypatch):
    from distributed_learning_simulator_tpu import simulator

    cfg = dataclasses.replace(
        tiny_config, round=2, telemetry_level="basic",
        compilation_cache_dir=None, log_root=str(tmp_path / "log"),
        checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=1,
    )
    simulator.run_simulation(cfg)
    first = spans.last_run()

    # The resumed run is made to recompile: every cache dropped after
    # round 2's checkpoint, so a later round traces and compiles again.
    real_crash = simulator.maybe_crash

    def drop_caches(round_idx):
        if round_idx == 2:
            jax.clear_caches()
        real_crash(round_idx)

    monkeypatch.setattr(simulator, "maybe_crash", drop_caches)
    logged = []

    class _Names(logging.Handler):
        def emit(self, record):
            m = _FINISHED_RE.search(record.getMessage())
            if m:
                logged.append((clock.monotonic(), m.group(1)))

    handler = _Names()
    dispatch = logging.getLogger("jax._src.dispatch")
    dispatch.addHandler(handler)
    was = bool(jax.config.jax_log_compiles)
    jax.config.update("jax_log_compiles", True)
    try:
        result = simulator.run_simulation(
            dataclasses.replace(cfg, round=6, resume=True)
        )
    finally:
        jax.config.update("jax_log_compiles", was)
        dispatch.removeHandler(handler)
    rec = spans.last_run()

    for run in (first, rec):
        by_id = {s["id"]: s for s in run.spans()}
        saves = [s for s in run.spans() if s["name"].startswith("save/")]
        assert sorted({s["name"] for s in saves}) == ["save/fetch",
                                                      "save/write"]
        for s in saves:
            assert by_id[s["parent"]]["name"] == "checkpoint"
            assert by_id[s["parent"]]["round"] == s["round"]
    by_id = {s["id"]: s for s in rec.spans()}
    resume = [s for s in rec.spans() if s["name"].startswith("resume/")]
    assert {s["name"] for s in resume} == {"resume/read", "resume/place"}
    for s in resume:
        assert by_id[s["parent"]]["name"] == "setup/resume"
    assert [r["round"] for r in result["history"]] == [2, 3, 4, 5]

    # The records' names are the log's, over the loop the monitor counts.
    assert result["post_warmup_compiles"] > 0
    rounds = [s for s in rec.spans() if s["name"] == "round"]
    t0, t1 = rounds[0]["t0"], rounds[-1]["t0"] + rounds[-1]["dur"]
    compiled = [n for r in result["history"]
                for n in r["telemetry"].get("compiled", [])]
    assert compiled == [n for t, n in logged if t0 <= t <= t1]
    assert sum(r["telemetry"]["compiles"] for r in result["history"]) == len(
        compiled
    )
    assert "round_fn" in [n for r in result["history"][1:]
                          for n in r["telemetry"].get("compiled", [])]


# ----------------------------------------------------------------------
# the benchmark's five readers


@pytest.fixture(scope="module")
def readers():
    sys.path.insert(0, BENCH_DIR)
    try:
        out = {}
        for name in FIVE:
            spec = importlib.util.spec_from_file_location(
                f"bench_metrics_{name}",
                os.path.join(BENCH_DIR, "metrics", name + ".py"),
            )
            out[name] = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(out[name])
        yield out
    finally:
        sys.path.remove(BENCH_DIR)


@pytest.fixture
def last_run(monkeypatch):
    def set_to(rec):
        monkeypatch.setattr(spans, "last_run", lambda: rec)

    return set_to


def _span(rec, name, t0, t1, cat="setup", **kw):
    sid = _at(t0, rec.begin, name, cat, **kw)
    return _at(t1, rec.end, sid)


def _recorder():
    """The window opens at 150.0::

        110-120  setup/resume: resume/read 110-117, resume/place 117-119.5
        121-127  round_fn traced [121,125] (inner [122,123] inside it),
                 lowered [125,127]; compiled [127,130], a miss
        105.5    an op's compile, no cache; 133 server_eval loaded, a hit
        140      the first round completes
        141-146  checkpoint: save/fetch 141-143, save/write 143-145.9
        147-149  round_fn compiled again, a miss; traced [149,151]
        151-152  a miss after the opening
    """
    rec = SpanRecorder()
    mon = rec.monitor
    sid = _at(108.0, rec.begin, "setup/resume", "setup")
    _span(rec, "resume/read", 110.0, 117.0)
    _span(rec, "resume/place", 117.0, 119.5)
    _at(120.0, rec.end, sid)

    def compile_(t_end, secs, fun, outcome=None):
        if outcome is not None:
            mon._on_cache(outcome)
        _at(t_end, mon._on_duration, COMPILE, secs, fun_name=fun)

    compile_(105.5, 0.5, "jit(broadcast_in_dim)")
    _at(123.0, mon._on_duration, TRACE, 1.0, fun_name="inner")
    _at(125.0, mon._on_duration, TRACE, 4.0, fun_name="round_fn")
    _at(127.0, mon._on_duration, LOWER, 2.0, fun_name="jit(round_fn)")
    compile_(130.0, 3.0, "jit(round_fn)", MISS)
    _at(132.0, mon._on_duration, TRACE, 1.0, fun_name="server_eval")
    compile_(133.0, 1.0, "jit(server_eval)", HIT)
    rec.round_done(0, 140.0)
    sid = _at(141.0, rec.begin, "checkpoint", "host", round_idx=0)
    _span(rec, "save/fetch", 141.0, 143.0, "host", round_idx=0)
    _span(rec, "save/write", 143.0, 145.9, "host", round_idx=0)
    _at(146.0, rec.end, sid)
    compile_(149.0, 2.0, "jit(round_fn)", MISS)
    _at(151.0, mon._on_duration, TRACE, 2.0, fun_name="round_fn")
    compile_(152.0, 1.0, "jit(round_fn)", MISS)
    return rec


CTX = {"opened_at": 150.0, "setup_s": 50.0,
       "trace": {"round_module": "jit_round_fn"}}
EXPECTED = {
    # [121,125] + [125,127] + [149,150] (clipped at the opening).
    "setup_round_program_trace_lower_s": 7.0,
    # [127,130] + [147,149]; the one after the opening is not set-up.
    "setup_compile_cold_s": 5.0,
    "setup_resume_read_s": 7.0,
    "setup_resume_place_s": 2.5,
    "setup_save_s": 4.9,
}


@pytest.mark.parametrize("name", FIVE)
def test_reader_on_a_hand_fed_recorder(readers, last_run, name):
    last_run(_recorder())
    assert readers[name].read(dict(CTX)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", FIVE)
def test_reader_without_a_recorder_reads_nothing(readers, last_run,
                                                 monkeypatch, name):
    """telemetry off, and a program older than the recorder: no value,
    no exception."""
    last_run(None)
    assert readers[name].read(dict(CTX)) is None
    monkeypatch.delattr(spans, "last_run")
    assert readers[name].read(dict(CTX)) is None


@pytest.mark.parametrize("name", FIVE)
def test_reader_over_a_program_without_the_ledger(readers, last_run, name):
    """The parent of this ledger: a recorder whose events are plain
    tuples, with no ``programs()`` and none of the four spans."""
    rec = _recorder()
    last_run(types.SimpleNamespace(
        spans=lambda: [s for s in rec.spans()
                       if not s["name"].startswith(("resume/", "save/"))],
        round_stamps=rec.round_stamps,
        duration_events=lambda: [tuple(e) for e in rec.duration_events()],
        main_thread=rec.main_thread, evicted_until=None,
    ))
    assert readers[name].read(dict(CTX)) is None


@pytest.mark.parametrize("name", FIVE)
def test_reader_never_reads_over_an_evicted_ring(readers, last_run, name):
    """Once the ring has evicted anything, the readers of what sits in it
    (the compiles and traces after the first round, the followed rounds'
    save) give no number; the resume's spans are set-up's, never
    evicted, and read on."""
    rec = _recorder()
    rec._evicted_until = 146.0
    last_run(rec)
    got = readers[name].read(dict(CTX))
    if name.startswith("setup_resume_"):
        assert got == pytest.approx(EXPECTED[name])
    else:
        assert got is None


def test_untraced_run_has_no_round_program(readers, last_run):
    last_run(_recorder())
    ctx = dict(CTX, trace=None)
    assert readers["setup_round_program_trace_lower_s"].read(ctx) is None
    assert readers["setup_compile_cold_s"].read(ctx) == pytest.approx(5.0)


def test_benchmark_json_lists_the_five():
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in FIVE}
    for name in FIVE:
        entry = entries[name]
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves"}
        assert (entry["unit"], entry["better"], entry["moves"]) == (
            "s", "lower", "setup_s"
        )
        assert entry["layer"] in layers
        assert os.path.exists(
            os.path.join(BENCH_DIR, "metrics", name + ".py")
        )
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(FIVE[0])
    assert names[first:first + 5] == list(FIVE)
