"""The benchmark's set-up and host-loop metric readers
(``benchmark/metrics/<name>.py``, each loaded by path as ``run.py`` loads
it) on a synthetic recorder and ``ctx``: every number below is arithmetic
on hand-made spans, none a measurement.

The timeline (seconds on the recorder's clock; the process started at
100.0 and the window opened at 150.0, so ``setup_s`` is 50)::

    108        run begins (8 s of imports, data and weights before it)
    108-109    setup/entry
    109-112    setup/data        |  130-132  setup/data (placement)
    112-128    setup/model_init  |  128.5-129 setup/model_init (state)
    128-128.5  setup/build       |  132-133  setup/build
    129-130    setup/resume
    133-133.5  (nothing: unattributed)
    133.5-141  round r0 (compiles), 141-145 round r1,
    145-152    round r2: pipelined, it fetches and checkpoints r1, the
               last followed round, and straddles the opening at 150
"""

import importlib.util
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")

NINE = (
    "setup_before_program_s", "setup_data_s", "setup_model_init_s",
    "setup_resume_s", "setup_rounds_s", "setup_trace_lower_s",
    "setup_unattributed_s", "host_work_ms_per_round",
    "host_syncs_per_round",
)
MAIN, WORKER = 11, 22


@pytest.fixture(scope="module")
def readers():
    """The reader files, loaded by path with ``benchmark/`` importable
    (they share ``harness/hostspans.py``), as ``run.py`` has it."""
    sys.path.insert(0, BENCH_DIR)
    try:
        out = {}
        for name in NINE:
            spec = importlib.util.spec_from_file_location(
                f"bench_metrics_{name}",
                os.path.join(BENCH_DIR, "metrics", name + ".py"),
            )
            out[name] = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(out[name])
        yield out
    finally:
        sys.path.remove(BENCH_DIR)


def _span(sid, name, t0, t1, parent=0, round_idx=None, thread=MAIN):
    s = {"id": sid, "name": name, "t0": t0, "dur": t1 - t0,
         "parent": parent, "thread": thread}
    if round_idx is not None:
        s["round"] = round_idx
    return s


def _recorder(pipelined=True):
    spans = [
        _span(0, "run", 108.0, 175.0, parent=None),
        _span(1, "setup/entry", 108.0, 109.0),
        _span(2, "setup/data", 109.0, 112.0),
        _span(3, "setup/model_init", 112.0, 128.0),
        _span(4, "setup/build", 128.0, 128.5),
        _span(5, "setup/model_init", 128.5, 129.0),
        _span(6, "setup/resume", 129.0, 130.0),
        _span(7, "setup/data", 130.0, 132.0),
        _span(8, "setup/build", 132.0, 133.0),
        # 133.0-133.5: no span.
        _span(9, "round", 133.5, 141.0, round_idx=0),
        _span(10, "round", 141.0, 145.0, round_idx=1),
        _span(11, "round", 145.0, 152.0, round_idx=2),
        # Inside the loop (not top level): never counted as set-up cover.
        _span(12, "dispatch", 133.5, 140.0, parent=9, round_idx=0),
        _span(13, "host_sync", 141.5, 144.0, parent=10, round_idx=0),
        _span(14, "host_sync", 145.5, 149.0, parent=11, round_idx=1),
        _span(15, "checkpoint", 149.2, 150.5, parent=11, round_idx=1),
    ]
    if pipelined:
        # Window rounds r2..r4, 2.0 s of device each; iteration r+1 waits
        # for round r in its host_sync, 10 ms after it began; 20 ms of
        # post_round + record follow the stamp-to-be.
        spans += [
            _span(20, "round", 152.0, 154.02, round_idx=3),
            _span(21, "host_sync", 152.01, 154.0, parent=20, round_idx=2),
            _span(22, "round", 154.02, 156.02, round_idx=4),
            _span(23, "host_sync", 154.03, 156.0, parent=22, round_idx=3),
            # After the loop: the last deferred fetch, a top-level child.
            _span(24, "finalize", 156.02, 158.01, round_idx=4),
            _span(25, "host_sync", 156.03, 158.0, parent=24, round_idx=4),
            # The prefetch worker's thread also "syncs": not the main
            # thread's, never counted.
            _span(26, "host_sync", 152.0, 158.0, parent=None, round_idx=3,
                  thread=WORKER),
        ]
        stamps = [(0, 144.1), (1, 149.1), (2, 154.0), (3, 156.0),
                  (4, 158.0)]
    else:
        stamps = [(0, 144.1), (1, 149.1)]
    events = [
        ("trace_s", 113.0, 0.5),    # inner trace [112.5, 113.0]
        ("trace_s", 114.0, 2.0),    # outer trace [112.0, 114.0]
        ("trace_s", 138.0, 4.0),    # round 0: [134, 138]
        ("trace_s", 139.0, 0.5),    # traced inside the lowering below
        ("lower_s", 140.0, 2.0),    # [138, 140]
        ("compile_s", 141.0, 1.0),
        ("lower_s", 151.0, 2.0),    # straddles the opening: [149, 151]
    ]
    return types.SimpleNamespace(
        spans=lambda: list(spans), round_stamps=lambda: list(stamps),
        duration_events=lambda: list(events), main_thread=MAIN,
    )


CTX = {"opened_at": 150.0, "setup_s": 50.0}


@pytest.fixture
def last_run(monkeypatch):
    from distributed_learning_simulator_tpu.telemetry import spans

    def set_to(rec):
        monkeypatch.setattr(spans, "last_run", lambda: rec)

    return set_to


EXPECTED = {
    "setup_before_program_s": 8.0,
    "setup_data_s": 5.0,
    "setup_model_init_s": 16.5,
    "setup_resume_s": 1.0,
    # 7.5 + 4 + the 5 s of round r2's iteration before the opening.
    "setup_rounds_s": 16.5,
    # Unions: [112,114] + [134,140] + [149,150] (clipped at the opening).
    "setup_trace_lower_s": 9.0,
    "setup_unattributed_s": 0.5,
    # Window 150 -> 158 (last stamp); the main thread waited in
    # [152.01,154] + [154.03,156] + [156.03,158] = 5.93 s; 3 rounds.
    "host_work_ms_per_round": 1e3 * (8.0 - 5.93) / 3,
    "host_syncs_per_round": 1.0,
}


@pytest.mark.parametrize("name", NINE)
def test_reader_on_a_synthetic_recorder(readers, last_run, name):
    last_run(_recorder())
    assert readers[name].read(dict(CTX)) == pytest.approx(EXPECTED[name])


def test_the_set_up_metrics_add_up_to_setup_s(readers, last_run):
    """before + data + model init + resume + rounds + (setup/build and
    setup/entry, which have no metric) + unattributed = setup_s."""
    last_run(_recorder())
    got = {n: readers[n].read(dict(CTX)) for n in NINE}
    build_and_entry = 0.5 + 1.0 + 1.0
    assert (
        got["setup_before_program_s"] + got["setup_data_s"]
        + got["setup_model_init_s"] + got["setup_resume_s"]
        + got["setup_rounds_s"] + build_and_entry
        + got["setup_unattributed_s"]
    ) == pytest.approx(CTX["setup_s"])
    assert got["setup_trace_lower_s"] <= (
        got["setup_model_init_s"] + got["setup_rounds_s"]
    )


@pytest.mark.parametrize("name", NINE)
def test_reader_without_a_recorder_reads_nothing(readers, last_run,
                                                 monkeypatch, name):
    """telemetry off (``last_run()`` is None), and a program older than
    the recorder (no ``last_run`` at all): no value, no exception."""
    from distributed_learning_simulator_tpu.telemetry import spans

    last_run(None)
    assert readers[name].read(dict(CTX)) is None
    monkeypatch.delattr(spans, "last_run")
    assert readers[name].read(dict(CTX)) is None


def test_window_metrics_need_a_completed_round(readers, last_run):
    """A window in which no round completed has no per-round number."""
    last_run(_recorder(pipelined=False))
    assert readers["host_work_ms_per_round"].read(dict(CTX)) is None
    assert readers["host_syncs_per_round"].read(dict(CTX)) is None
    # The set-up metrics do not depend on the window.
    assert readers["setup_rounds_s"].read(dict(CTX)) == pytest.approx(16.5)


@pytest.mark.parametrize("name", NINE)
def test_reader_never_reads_over_evicted_spans(readers, last_run, name):
    """The ring is bounded: once it has evicted spans a reader needs
    (here up to 153.0, inside the window), the reader gives no number
    rather than one computed over the holes. The set-up sections are
    never evicted, the stamps and events are not the ring's: readers of
    those alone read on."""
    rec = _recorder()
    rec.evicted_until = 153.0
    last_run(rec)
    got = readers[name].read(dict(CTX))
    if name in ("setup_rounds_s", "setup_unattributed_s",
                "host_work_ms_per_round", "host_syncs_per_round"):
        assert got is None
    else:
        assert got == pytest.approx(EXPECTED[name])
    # Evicted, but only before the window opened: the window's readers
    # have all they need.
    rec.evicted_until = 149.5
    got = readers[name].read(dict(CTX))
    if name in ("setup_rounds_s", "setup_unattributed_s"):
        assert got is None
    else:
        assert got == pytest.approx(EXPECTED[name])


def test_a_second_fetch_a_round_shows(readers, last_run):
    """host_syncs_per_round is what guards "a single device_get a
    round": a second fetch inside the window's rounds reads above 1."""
    rec = _recorder()
    extra = [_span(40 + i, "host_sync", t, t + 0.001, parent=20,
                   round_idx=9) for i, t in enumerate((152.5, 154.5, 156.5))]
    spans = rec.spans() + extra
    rec.spans = lambda: spans
    last_run(rec)
    assert readers["host_syncs_per_round"].read(dict(CTX)) == 2.0


def test_benchmark_json_lists_the_nine():
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for name in NINE:
        entry = entries[name]
        assert entry["better"] == "lower" and "workloads" not in entry
        assert entry["moves"] in e2e
        assert entry["source"] in ("program_span", "program_counter")
        assert os.path.exists(
            os.path.join(BENCH_DIR, "metrics", name + ".py")
        )
