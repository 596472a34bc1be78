"""Stitch per-host span journals into one cross-host timeline.

Each host of a ``span_trace='on'`` run writes its own
``spans_<host_id>.jsonl`` (telemetry/spans.py): spans stamped with that
host's PRIVATE monotonic clock. This tool is the read side — it aligns
every host onto host 0's wall clock and emits one merged view:

    python scripts/trace_timeline.py DIR [DIR|FILE ...]
        [--out trace.json] [--json] [--host H]

* positional args — artifact/span directories (globbed for
  ``spans_*.jsonl``) or explicit journal files. Pass every host's
  journal (a shared ``span_dir`` makes this one directory).
* ``--out trace.json`` — write a Chrome trace-event file: load it in
  Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``; one
  process row per host, ``main`` + ``prefetch`` threads.
* ``--json`` — machine-readable summary on stdout instead of text.
* ``--host H`` — restrict the text/JSON summary to one host (the trace
  file always carries every host: a one-host timeline can't show skew).

Clock alignment: every journal header carries back-to-back
``epoch_wall``/``epoch_mono`` anchors plus ``clock_offset_s`` — this
host's wall clock minus host 0's, estimated once at the
``jax.distributed`` init barrier (parallel/multihost.py
``estimate_clock_alignment``) — and ``clock_uncertainty_s``, the
measured barrier RTT that bounds the estimate. A monotonic stamp t
aligns as::

    aligned = (t - epoch_mono) + epoch_wall - clock_offset_s

so all hosts land on host 0's wall timeline, good to ~the barrier RTT
(microseconds on a LAN; the summary prints the uncertainty so nobody
over-reads sub-RTT skews).

The summary computes, per round, ``barrier_skew_ms`` per barrier (the
max-minus-min host arrival the wait spans measured) and names the
slowest host — on a wait span the SHORTEST wait marks the host everyone
else waited for. Run totals give each host's DCN-wait vs busy split and
its share of the summed busy time (critical-path share). Unmatched
``open`` lines, ``inflight`` lines, and ``flight`` markers become the
postmortem section: what each host was doing when it died or was told
to stop (docs/OBSERVABILITY.md § Distributed tracing).

Deliberately imports nothing heavy (no jax, no telemetry package): the
journals are plain JSONL and this must run on a laptop holding only the
artifact files. Self-tested jax-free in tests/test_spans.py.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

#: Span categories counted as BUSY time (vs dcn_wait, which is idle
#: time spent waiting for other hosts at a barrier).
BUSY_CATS = ("phase", "dcn", "io", "stream", "round")


def find_journals(paths: list[str]) -> list[str]:
    """Expand directories to their spans_*.jsonl files; keep files."""
    out: list[str] = []
    for p in paths:
        if os.path.isdir(p):
            out.extend(sorted(glob.glob(os.path.join(p, "spans_*.jsonl"))))
        elif os.path.exists(p):
            out.append(p)
        else:
            raise FileNotFoundError(p)
    # De-dup while preserving order (a dir + an explicit file may overlap).
    seen: set[str] = set()
    uniq = []
    for p in out:
        if p not in seen:
            seen.add(p)
            uniq.append(p)
    return uniq


def load_journal(path: str) -> dict:
    """Parse one host journal into {header, spans, events, opens,
    inflight, flights}. Tolerates a torn final line (SIGKILL mid-write)."""
    header = None
    spans: list[dict] = []
    events: list[dict] = []
    opens: dict[int, dict] = {}
    inflight: list[dict] = []
    flights: list[dict] = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue  # torn tail line from a killed process
            kind = rec.get("kind")
            if kind == "header":
                header = rec
            elif kind == "span":
                spans.append(rec)
                opens.pop(rec.get("id"), None)
            elif kind == "event":
                events.append(rec)
            elif kind == "open":
                opens[rec.get("id")] = rec
            elif kind == "inflight":
                inflight.append(rec)
                opens.pop(rec.get("id"), None)
            elif kind == "flight":
                flights.append(rec)
    if header is None:
        raise ValueError(f"{path}: no header line — not a span journal")
    return {
        "path": path,
        "header": header,
        "spans": spans,
        "events": events,
        # Opens never matched by a span/inflight line: the process died
        # inside them with no cleanup — the hard-kill postmortem signal.
        "unmatched_opens": list(opens.values()),
        "inflight": inflight,
        "flights": flights,
    }


def aligner(header: dict):
    """Monotonic stamp -> host-0 wall seconds (see module docstring)."""
    epoch_mono = float(header["epoch_mono"])
    epoch_wall = float(header["epoch_wall"])
    offset = float(header.get("clock_offset_s", 0.0))

    def align(t: float) -> float:
        return (t - epoch_mono) + epoch_wall - offset

    return align


# ----------------------------------------------------------------------
# Chrome trace-event emission


def chrome_trace(journals: list[dict]) -> dict:
    """Merge journals into a Chrome trace-event JSON object (perfetto/
    chrome://tracing loadable). One process per host; the streaming
    prefetch worker gets its own thread row."""
    out: list[dict] = []
    t0 = None  # earliest aligned stamp across hosts -> trace origin
    prepared = []
    for j in journals:
        align = aligner(j["header"])
        host = int(j["header"]["host_id"])
        last = None
        rows = []
        for s in j["spans"]:
            ts = align(s["t0"])
            rows.append(("X", s, ts, float(s.get("dur", 0.0))))
            last = ts + float(s.get("dur", 0.0)) if last is None else max(
                last, ts + float(s.get("dur", 0.0)))
        for e in j["events"]:
            ts = align(e["t"])
            rows.append(("i", e, ts, 0.0))
            last = ts if last is None else max(last, ts)
        # A span the host died inside: draw it to the last stamp the
        # journal saw so the kill moment is visible on the timeline.
        for s in j["unmatched_opens"] + j["inflight"]:
            ts = align(s["t0"])
            end = last if last is not None and last > ts else ts
            rows.append(("X", {**s, "inflight": True}, ts, end - ts))
        prepared.append((host, j, rows))
        for _, _, ts, _ in rows:
            t0 = ts if t0 is None else min(t0, ts)
    if t0 is None:
        t0 = 0.0
    for host, j, rows in prepared:
        out.append({"ph": "M", "name": "process_name", "pid": host,
                    "tid": 0, "args": {"name": f"host {host}"}})
        out.append({"ph": "M", "name": "thread_name", "pid": host,
                    "tid": 0, "args": {"name": "main"}})
        out.append({"ph": "M", "name": "thread_name", "pid": host,
                    "tid": 1, "args": {"name": "prefetch"}})
        for ph, rec, ts, dur in rows:
            tid = 1 if rec.get("cat") == "stream" else 0
            ev = {
                "name": rec.get("name", "?"),
                "cat": rec.get("cat", "?"),
                "ph": ph,
                "ts": round((ts - t0) * 1e6, 3),
                "pid": host,
                "tid": tid,
            }
            if ph == "X":
                ev["dur"] = round(dur * 1e6, 3)
            else:
                ev["s"] = "t"
            args = dict(rec.get("attrs") or {})
            if rec.get("round") is not None:
                args["round"] = rec["round"]
            if rec.get("inflight"):
                args["inflight"] = True
            if args:
                ev["args"] = args
            out.append(ev)
    return {"traceEvents": out, "displayTimeUnit": "ms"}


# ----------------------------------------------------------------------
# summary analytics


def _wait_groups(journals: list[dict]) -> dict:
    """(round, barrier name) -> [(host, wait dur s, skew_ms attr)]."""
    groups: dict[tuple, list] = {}
    for j in journals:
        host = int(j["header"]["host_id"])
        for s in j["spans"]:
            if s.get("cat") != "dcn_wait":
                continue
            key = (s.get("round"), s.get("name"))
            attrs = s.get("attrs") or {}
            groups.setdefault(key, []).append(
                (host, float(s.get("dur", 0.0)), attrs.get("skew_ms"))
            )
    return groups


def summarize(journals: list[dict], host: int | None = None) -> dict:
    """The cross-host analytics block: per-round barrier skews with the
    slowest host named, per-host busy/wait totals + critical-path share,
    and the postmortem section."""
    hosts = []
    totals: dict[int, dict] = {}
    for j in journals:
        h = j["header"]
        hid = int(h["host_id"])
        busy = 0.0
        wait = 0.0
        by_cat: dict[str, float] = {}
        for s in j["spans"]:
            cat = s.get("cat", "?")
            dur = float(s.get("dur", 0.0))
            by_cat[cat] = by_cat.get(cat, 0.0) + dur
            if cat == "dcn_wait":
                wait += dur
            elif cat in BUSY_CATS and cat != "round":
                # 'round' is the envelope span; counting it would double
                # count the phases nested inside it.
                busy += dur
        totals[hid] = {"busy_s": busy, "dcn_wait_s": wait,
                       "by_cat": by_cat}
        hosts.append({
            "host_id": hid,
            "n_hosts": int(h.get("n_hosts", 1)),
            "pid": h.get("pid"),
            "journal": j["path"],
            "clock_offset_s": h.get("clock_offset_s", 0.0),
            "clock_uncertainty_s": h.get("clock_uncertainty_s", 0.0),
            "spans": len(j["spans"]),
            "events": len(j["events"]),
            # Build-time counts the recorder journaled (set_counter):
            # ``local_steps_unrolled``.
            "counters": {
                e["name"]: e.get("attrs", {}).get("value")
                for e in j["events"] if e.get("cat") == "counter"
            },
        })
    busy_sum = sum(t["busy_s"] for t in totals.values())
    for hid, t in totals.items():
        denom = t["busy_s"] + t["dcn_wait_s"]
        t["wait_fraction"] = round(t["dcn_wait_s"] / denom, 4) if denom else 0.0
        t["critical_path_share"] = (
            round(t["busy_s"] / busy_sum, 4) if busy_sum else 0.0
        )
        t["busy_s"] = round(t["busy_s"], 6)
        t["dcn_wait_s"] = round(t["dcn_wait_s"], 6)
        t["by_cat"] = {k: round(v, 6) for k, v in sorted(t["by_cat"].items())}

    rounds: dict[int, dict] = {}
    for (rnd, name), members in sorted(
        _wait_groups(journals).items(),
        key=lambda kv: (kv[0][0] is None, kv[0]),
    ):
        skews = [m[2] for m in members if m[2] is not None]
        skew_ms = max(skews) if skews else None
        # The host that waited LEAST arrived last: everyone else's wait
        # span was open until it showed up.
        slowest = min(members, key=lambda m: m[1])[0] if len(members) > 1 \
            else None
        entry = {"skew_ms": skew_ms, "slowest_host": slowest,
                 "waits": {m[0]: round(m[1], 6) for m in sorted(members)}}
        rkey = -1 if rnd is None else int(rnd)
        rounds.setdefault(rkey, {})[name] = entry

    postmortem = []
    for j in journals:
        hid = int(j["header"]["host_id"])
        align = aligner(j["header"])
        for f in j["flights"]:
            entry = {
                "host_id": hid, "kind": "flight",
                "reason": f.get("reason"),
                "t_aligned": round(align(f["t"]), 6),
            }
            # A crash that unwound through spans closed them before the
            # flight flush; the recorder stamps the innermost one here
            # so the postmortem still names where the failure struck.
            in_span = f.get("in_span")
            if isinstance(in_span, dict):
                entry["name"] = in_span.get("name")
                entry["cat"] = in_span.get("cat")
                entry["round"] = in_span.get("round")
                entry["error"] = in_span.get("error")
            postmortem.append(entry)
        for s in j["inflight"] + j["unmatched_opens"]:
            postmortem.append({
                "host_id": hid,
                # An unmatched open means the process never got to write
                # anything more — the hard-kill case; 'inflight' lines
                # come from the soft paths (SIGTERM, crash, quorum).
                "kind": ("inflight" if s.get("inflight")
                         else "died_inside"),
                "name": s.get("name"), "cat": s.get("cat"),
                "round": s.get("round"),
                "t0_aligned": round(align(s["t0"]), 6),
            })
    postmortem.sort(key=lambda p: p.get("t_aligned") or p.get("t0_aligned")
                    or 0.0)

    if host is not None:
        hosts = [h for h in hosts if h["host_id"] == host]
        totals = {k: v for k, v in totals.items() if k == host}
        postmortem = [p for p in postmortem if p["host_id"] == host]

    return {
        "hosts": hosts,
        "totals": {str(k): v for k, v in sorted(totals.items())},
        "rounds": {str(k): v for k, v in sorted(rounds.items())},
        "postmortem": postmortem,
    }


def render_text(summary: dict) -> str:
    lines = []
    lines.append("== hosts ==")
    for h in summary["hosts"]:
        lines.append(
            f"  host {h['host_id']}/{h['n_hosts']}: {h['spans']} spans, "
            f"{h['events']} events, clock offset "
            f"{h['clock_offset_s'] * 1e3:+.3f} ms "
            f"(+/- {h['clock_uncertainty_s'] * 1e3:.3f} ms) "
            f"[{os.path.basename(h['journal'])}]"
        )
        for name, value in sorted(h["counters"].items()):
            lines.append(f"    {name}: {value}")
    lines.append("== totals ==")
    for hid, t in summary["totals"].items():
        lines.append(
            f"  host {hid}: busy {t['busy_s']:.3f}s, dcn wait "
            f"{t['dcn_wait_s']:.3f}s (wait fraction {t['wait_fraction']:.1%},"
            f" critical-path share {t['critical_path_share']:.1%})"
        )
    if summary["rounds"]:
        lines.append("== barrier skew by round ==")
        for rnd, barriers in summary["rounds"].items():
            for name, e in sorted(barriers.items()):
                skew = ("n/a" if e["skew_ms"] is None
                        else f"{e['skew_ms']:.3f} ms")
                slow = ("" if e["slowest_host"] is None
                        else f", slowest host {e['slowest_host']}")
                lines.append(f"  round {rnd} {name}: skew {skew}{slow}")
    if summary["postmortem"]:
        lines.append("== postmortem ==")
        for p in summary["postmortem"]:
            if p["kind"] == "flight":
                struck = "" if not p.get("name") else (
                    f" while in {p['cat']}:{p['name']}"
                    + ("" if p.get("round") is None
                       else f" (round {p['round']})")
                )
                lines.append(
                    f"  host {p['host_id']}: flight recorder flushed "
                    f"({p['reason']}){struck}"
                )
            else:
                where = "" if p.get("round") is None else \
                    f" (round {p['round']})"
                verb = ("in flight" if p["kind"] == "inflight"
                        else "DIED INSIDE")
                lines.append(
                    f"  host {p['host_id']}: {verb} "
                    f"{p['cat']}:{p['name']}{where}"
                )
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="Stitch spans_*.jsonl host journals into one "
                    "cross-host timeline + skew/postmortem summary.",
    )
    ap.add_argument("paths", nargs="+",
                    help="span directories and/or journal files")
    ap.add_argument("--out", default=None,
                    help="write a Chrome trace-event JSON (perfetto)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable summary on stdout")
    ap.add_argument("--host", type=int, default=None,
                    help="restrict the summary to one host id")
    args = ap.parse_args(argv)

    try:
        paths = find_journals(args.paths)
    except FileNotFoundError as e:
        print(f"error: no such path: {e}", file=sys.stderr)
        return 2
    if not paths:
        print("error: no spans_*.jsonl journals found", file=sys.stderr)
        return 2
    journals = [load_journal(p) for p in paths]

    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(chrome_trace(journals), f)
        print(f"wrote {args.out} ({len(journals)} hosts) — load in "
              "https://ui.perfetto.dev", file=sys.stderr)

    summary = summarize(journals, host=args.host)
    if args.json:
        json.dump(summary, sys.stdout, indent=1)
        print()
    else:
        print(render_text(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
