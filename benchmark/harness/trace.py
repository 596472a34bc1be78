"""Reduction of a ``jax.profiler`` trace to the numbers the metrics read.

Kept with the benchmark so every PR computes them alike (the program's
``utils/tracing`` does the like for its own reports and may change; PERF.md
§ 7 lists it). Reads the trace-viewer JSON the profiler writes beside the
``.xplane.pb``: only there do device ops carry ``raw_bytes_accessed``.

What a v5e trace looks like (looked at by hand, PR 24): one process per
chip named ``/device:TPU:<n>``; its thread ``XLA Modules`` has one event
per executed program (``jit_round_fn(<hash>)``), ``XLA Ops`` one per HLO
op, parents included (``while``, ``conditional`` and ``call`` frames span
their children), ``Async XLA Ops`` the in-flight spans of asynchronous
copies and collectives. Numbers in ``args`` arrive as strings.

The JSON holds at most 1,000,000 events and drops the rest in silence, so
a trace that reaches the cap is never reduced from it: :func:`load` then
reads the ``.xplane.pb`` itself (every event, but no byte counts: the
metrics that need bytes then have nothing to read).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

EVENT_CAP = 1_000_000
_FRAMES = ("while", "conditional", "call")
_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)


def _one(trace_dir: str, pattern: str) -> str:
    paths = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", pattern
    ))
    if len(paths) != 1:
        raise RuntimeError(
            f"{trace_dir}: expected one profiling session, found "
            f"{len(paths)} ({pattern})"
        )
    return paths[0]


def load_json(trace_dir: str) -> dict:
    """The one session under ``trace_dir``: its trace-viewer JSON."""
    with gzip.open(_one(trace_dir, "*.trace.json.gz"), "rt") as f:
        return json.load(f)


def load_xplane(trace_dir: str) -> dict:
    """The same session read from its ``.xplane.pb``, in the JSON's shape
    (device planes only): every event is there, ``raw_bytes_accessed`` is
    not. An op's name is the left-hand side of its HLO text."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(_one(trace_dir, "*.xplane.pb"))
    events = []
    for pid, plane in enumerate(data.planes):
        if not plane.name.startswith("/device:TPU:"):
            continue
        events.append({"ph": "M", "pid": pid, "name": "process_name",
                       "args": {"name": plane.name}})
        for tid, line in enumerate(plane.lines):
            events.append({"ph": "M", "pid": pid, "tid": tid,
                           "name": "thread_name",
                           "args": {"name": line.name}})
            for ev in line.events:
                text = ev.name
                match = re.match(r"%(\S+) = ", text)
                events.append({
                    "ph": "X", "pid": pid, "tid": tid,
                    "ts": ev.start_ns / 1e3, "dur": ev.duration_ns / 1e3,
                    "name": match.group(1) if match else text,
                    "args": {"long_name": text[:200]},
                })
    return {"traceEvents": events}


def load(trace_dir: str) -> dict:
    """The JSON where it is whole, else the ``.xplane.pb``."""
    data = load_json(trace_dir)
    if len(data.get("traceEvents", [])) >= EVENT_CAP:
        data = load_xplane(trace_dir)
    return data


def _is_frame(ev: dict) -> bool:
    category = (ev.get("args") or {}).get("hlo_category", "")
    name = ev.get("name", "")
    return (
        category in _FRAMES or name.startswith(_FRAMES + ("jit(",))
    )


def _union(intervals: list[tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def reduce(data: dict, top: int = 10) -> dict | None:
    """Per-round device numbers over whole periods of the round program.

    The traced span starts and ends anywhere, so it is cut to whole
    periods of the longest-running program (the round), on each chip:
    from its second start in the trace to its last. Its first event is
    left out because a trace that starts mid-round shows that round cut
    short, starting when the trace did (seen on the v5e: a 2.06 s event
    before 2.17 s ones). ``None`` when no chip holds a whole period
    (nothing to normalise by). Times in seconds.
    """
    events = data.get("traceEvents", [])
    procs, threads = {}, {}
    for ev in events:
        if ev.get("ph") != "M":
            continue
        if ev.get("name") == "process_name":
            procs[ev["pid"]] = ev["args"]["name"]
        elif ev.get("name") == "thread_name":
            threads[(ev["pid"], ev["tid"])] = ev["args"]["name"]
    chips = sorted(
        pid for pid, name in procs.items() if name.startswith("/device:TPU:")
    )
    if not chips:
        return None
    lanes = {(pid, kind): [] for pid in chips
             for kind in ("XLA Modules", "XLA Ops", "Async XLA Ops")}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        lane = (ev.get("pid"), threads.get((ev.get("pid"), ev.get("tid"))))
        if lane in lanes:
            lanes[lane].append(ev)

    per_chip = []
    for pid in chips:
        modules = sorted(lanes[(pid, "XLA Modules")], key=lambda e: e["ts"])
        by_name: dict[str, float] = {}
        for m in modules:
            key = _module_name(m["name"])
            by_name[key] = by_name.get(key, 0.0) + float(m["dur"])
        if not by_name:
            return None
        round_module = max(by_name, key=by_name.get)
        starts = [m["ts"] for m in modules
                  if _module_name(m["name"]) == round_module][1:]
        if len(starts) < 2:
            return None
        per_chip.append({
            "pid": pid, "modules": modules, "round_module": round_module,
            "t0": starts[0], "t1": starts[-1], "periods": len(starts) - 1,
        })
    periods = min(c["periods"] for c in per_chip)

    op_time: dict[str, float] = {}
    gap_time: dict[str, float] = {}
    out_chips = []
    for c in per_chip:
        t0, t1 = c["t0"], c["t1"]
        ops = [
            e for e in lanes[(c["pid"], "XLA Ops")]
            if t0 <= e["ts"] < t1 and not _is_frame(e)
        ]
        intervals = [
            (e["ts"], min(e["ts"] + float(e["dur"]), t1)) for e in ops
        ]
        busy_us = _union(intervals)
        bytes_total = None  # stays None where no op carries the count
        collective_us = 0.0
        for e in ops:
            args = e.get("args") or {}
            if "raw_bytes_accessed" in args:
                bytes_total = (bytes_total or 0.0) + float(
                    args["raw_bytes_accessed"]
                )
            name = e.get("name", "<unnamed>")
            op_time[name] = op_time.get(name, 0.0) + float(e["dur"])
            if any(mark in name for mark in _COLLECTIVES):
                collective_us += float(e["dur"])
        async_collective_us = _union([
            (e["ts"], min(e["ts"] + float(e["dur"]), t1))
            for e in lanes[(c["pid"], "Async XLA Ops")]
            if t0 <= e["ts"] < t1
            and any(mark in e.get("name", "") for mark in _COLLECTIVES)
        ])
        # Idle gaps, named by the programs on either side of them.
        mods = [m for m in c["modules"] if t0 <= m["ts"] <= t1]
        for prev, nxt in zip(mods, mods[1:]):
            gap = nxt["ts"] - (prev["ts"] + float(prev["dur"]))
            if gap > 0:
                key = (f"{_module_name(prev['name'])}->"
                       f"{_module_name(nxt['name'])}")
                gap_time[key] = gap_time.get(key, 0.0) + gap
        out_chips.append({
            "chip": procs[c["pid"]],
            "window_s": (t1 - t0) / 1e6,
            "busy_s": busy_us / 1e6,
            "bytes": bytes_total,
            "ops": len(ops),
            "collective_s": collective_us / 1e6,
            "async_collective_s": async_collective_us / 1e6,
            "periods": c["periods"],
        })

    def ranked(table):
        return [
            [name, us / 1e6] for name, us in
            sorted(table.items(), key=lambda kv: (-kv[1], kv[0]))[:top]
        ]

    n = len(out_chips)
    return {
        "periods": periods,
        "round_module": per_chip[0]["round_module"],
        "chips": out_chips,
        "window_s": sum(c["window_s"] for c in out_chips) / n,
        "busy_s": sum(c["busy_s"] for c in out_chips) / n,
        "device_ops": ranked(op_time),
        "idle_gaps": ranked(gap_time),
    }
