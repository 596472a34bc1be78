"""The trace reduction on a small recorded v5e trace.

``fixtures/v5e_scan_trace.json.gz`` was recorded on a TPU v5e (PR 24):
four runs of a 40-step scan (a ``while`` frame spanning its children) each
followed by a small second program, device lanes only, arguments cut to
what the reduction reads. One op was added by hand, overlapping the first
op of the first whole period by half its length, so that a union of intervals
and a sum of durations differ. The numbers below are counts of that file
and have to repeat exactly; none is a measurement of anything.
"""

import gzip
import json
import os

import pytest
from conftest import BENCH_DIR

from harness import trace

FIXTURE = os.path.join(BENCH_DIR, "fixtures", "v5e_scan_trace.json.gz")


@pytest.fixture(scope="module")
def data():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


def test_reduction_repeats_exactly(data):
    with open(os.path.join(BENCH_DIR, "fixtures",
                           "v5e_scan_trace.expected.json")) as f:
        expected = json.load(f)
    assert trace.reduce(data) == expected


def test_frames_are_excluded_and_overlap_is_counted_once(data):
    got = trace.reduce(data)
    chip = got["chips"][0]
    events = [e for e in data["traceEvents"] if e.get("ph") == "X"]
    frames = [e for e in events if trace._is_frame(e)]
    assert frames and all(e["name"].startswith("while") for e in frames)
    assert not any(name.startswith("while") for name, _ in got["device_ops"])
    assert got["periods"] == 2 and got["round_module"] == "jit_round_fn"
    # Busy is a union: less than the summed durations by the overlap the
    # hand-added op brings, and never more than the window.
    summed = sum(s for _, s in trace.reduce(data, top=10**6)["device_ops"])
    assert 0 < summed - chip["busy_s"] < 1e-3
    assert chip["busy_s"] < chip["window_s"]
    assert chip["bytes"] > 0 and chip["collective_s"] == 0.0


def test_xplane_reads_the_same_times_and_no_bytes():
    """``fixtures/v5e_session`` is one whole recorded session (three runs
    of an 8-step scan): the JSON and the ``.xplane.pb`` give the same
    periods, window and busy time; only the JSON has bytes."""
    session = os.path.join(BENCH_DIR, "fixtures", "v5e_session")
    from_json = trace.reduce(trace.load_json(session))
    from_pb = trace.reduce(trace.load_xplane(session))
    assert from_json["periods"] == from_pb["periods"] == 1
    for key in ("window_s", "busy_s"):
        assert from_pb[key] == pytest.approx(from_json[key], rel=1e-4)
    assert [n for n, _ in from_pb["device_ops"]] == [
        n for n, _ in from_json["device_ops"]
    ]
    assert from_json["chips"][0]["bytes"] > 0
    assert from_pb["chips"][0]["bytes"] is None
    assert trace.load(session) == trace.load_json(session)


def test_no_device_lane_reads_nothing():
    assert trace.reduce({"traceEvents": []}) is None
