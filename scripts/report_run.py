"""Offline run reporter: render a run's artifacts dir into a summary.

    python scripts/report_run.py <artifacts_dir | metrics.jsonl>
        [--trace DIR] [--json OUT.json] [--top K]

Input is the per-run artifacts directory the simulator writes
(``log/<algo>/<dataset>/<model>/<run-id>_artifacts`` containing
``metrics.jsonl``) or a ``metrics.jsonl`` path directly. Renders a
terminal summary — accuracy curve, per-round phase-time breakdown,
compile events, rejected rounds, peak HBM, and (schema v3) a
client-health section: the anomaly-flag table, a divergence timeline
over the per-round update-norm spread, and per-client loss sparklines
when the records carry raw per-client values (cohorts up to the
per-client cap; telemetry/client_stats.py). Optionally writes the same
content as machine-readable JSON (``--json``). ``--trace`` points at a
``jax.profiler`` trace directory (``config.profile_dir``) and adds the
deterministic device-op totals plus top-ops-by-bytes AND
top-ops-by-time tables (same selection rule as bench.py's regression
proxy: utils/tracing.py), and the device's idle gaps named by the host
span that covers each (``utils/tracing.attribute_idle_gaps``: the
program's spans are in the capture's host plane, on its clock).

Reads all metrics schemas: v1 (pre-telemetry; accuracy/timing only), v2
(``telemetry`` sub-object), v3 (``client_stats`` sub-object), v4
(``async`` sub-object — rendered as the staleness section:
buffer-occupancy timeline, staleness histogram, simulated-clock speedup
vs sync; see docs/OBSERVABILITY.md), v5 (``stream`` sub-object —
rendered as an h2d transfer row under the phase table plus run-total
transfer accounting; client_residency='streamed',
docs/PERFORMANCE.md § Streamed client state), v6 (``costmodel``
sub-object — rendered as the "cost at scale" section: the roofline
model's predicted round time, bottleneck, and $/run across the
topology table, with this run's measured round as the anchor row;
telemetry/costmodel.py). ``--trace`` computes the same section LIVE
from the trace's categorized ledger when the records don't carry one
(``--cost-rounds`` sets the $/run horizon), and v7 (``valuation``
sub-object — rendered as the client-valuation section: latest
top-k/bottom-k client tables, the loss-delta curve, the
flagged-client overlay against the v3 client-health section, and the
latest GTG audit-correlation line; telemetry/valuation.py), and v8
(``sweep`` sub-object — rendered as the sweep section: per-point
accuracy table, winner line, compile-reuse summary, and — when a trace
is attached (``--trace``) — the cost model's $/sweep row per topology;
sweep/engine.py), and v9 (``population`` sub-object — rendered as the
dynamic-population section: alive-N-over-time sparkline, per-round
join/depart counts, churn-rejected rounds, and the planted
drift-cohort overlay against the v7 valuation top/bottom tables;
robustness/population.py), and v10 (``gtg`` sub-object — the
mesh-sharded GTG walk's per-round provenance; its audit-side face,
wall seconds + device count, rides the v7 valuation audit line;
algorithms/shapley.py), and v11 (``multihost`` sub-object — the
distributed shard store's per-host assembly provenance;
parallel/streaming.py), and v12 (``spans`` sub-object — rendered as
the distributed-trace section: per-round span counts, DCN wait vs
transfer split, and the barrier-skew timeline; telemetry/spans.py).
When ``spans_*.jsonl`` journals sit next to ``metrics.jsonl`` (or a
shared ``span_dir`` is passed via ``--spans``), the cross-host
timeline section is stitched live through ``scripts/trace_timeline.py``
— per-host span and event counts with the recorder's build-time
counters under them (``local_steps_unrolled``: how many local steps the
round program holds unrolled, 0 = a loop; ``head_backward_tied``: 1 where
the model's head makes its loss and its gradients itself and hands on no
logits), busy/wait totals, per-round
barrier skew with the slowest host named, and the flight-recorder
postmortem (what each host was doing when it died); ``--host``
restricts it to one host. The only
heavy import (jax, via utils.tracing) is deferred behind ``--trace``,
so metrics-only reporting is instant.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import trace_timeline  # noqa: E402  (scripts/trace_timeline.py, jax-free)

_SPARK = "▁▂▃▄▅▆▇█"


def sparkline(values: list[float]) -> str:
    """Unicode sparkline; constant series render flat, not empty."""
    if not values:
        return ""
    lo, hi = min(values), max(values)
    span = (hi - lo) or 1.0
    return "".join(
        _SPARK[int((v - lo) / span * (len(_SPARK) - 1))] for v in values
    )


def load_metrics(path: str) -> list[dict]:
    """Read metrics.jsonl records from a file or an artifacts dir."""
    if os.path.isdir(path):
        path = os.path.join(path, "metrics.jsonl")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"no metrics.jsonl at {path!r} — pass a run's artifacts dir "
            "(log/<algo>/<dataset>/<model>/<run-id>_artifacts) or the "
            "file itself"
        )
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                records.append(json.loads(line))
    return records


def summarize_client_health(records: list[dict]) -> dict | None:
    """Aggregate schema-v3 ``client_stats`` sub-objects into the
    client-health summary: per-round flag table, the update-norm
    divergence timeline, and per-client loss series when the records
    carry raw per-client values. None when no record has client stats."""
    cstats = [
        (r.get("round"), r["client_stats"]) for r in records
        if isinstance(r.get("client_stats"), dict)
    ]
    if not cstats:
        return None
    flagged_rounds = [
        {
            "round": rnd,
            "flagged": cs.get("flagged_clients", []),
            "reasons": cs.get("flag_reason", {}),
        }
        for rnd, cs in cstats if cs.get("flagged_clients")
    ]
    timeline = []
    for rnd, cs in cstats:
        un = (cs.get("quantiles") or {}).get("update_norm") or {}
        timeline.append({
            "round": rnd,
            "update_norm_p50": un.get("p50"),
            "update_norm_p100": un.get("p100"),
            "flagged": len(cs.get("flagged_clients") or []),
        })
    per_client_loss: dict[str, list] = {}
    for _, cs in cstats:
        pc = cs.get("per_client")
        if not pc:
            continue
        losses = pc.get("loss_after") or []
        for cid, loss in zip(pc.get("client_ids", []), losses):
            per_client_loss.setdefault(str(cid), []).append(loss)
    health: dict = {
        "rounds_reported": len(cstats),
        "total_flags": sum(len(f["flagged"]) for f in flagged_rounds),
        "flagged_rounds": flagged_rounds,
        "divergence_timeline": timeline,
    }
    if per_client_loss:
        health["per_client_loss"] = per_client_loss
    for key in ("quant_mse", "vote_agreement"):
        vals = [cs[key] for _, cs in cstats
                if isinstance(cs.get(key), (int, float))]
        if vals:
            health[key] = {
                "mean": round(statistics.mean(vals), 6),
                "last": round(vals[-1], 6),
            }
    return health


def summarize_valuation(records: list[dict],
                        flagged_ids: set[int] | None = None) -> dict | None:
    """Aggregate schema-v7 ``valuation`` sub-objects: the latest
    top-k/bottom-k client tables, the audit-correlation trail, and —
    when the records carry raw per-client values — the overlay against
    the client-health detector's flagged clients (an anomalous client
    should show a depressed valuation; agreement between the two
    independent signals is the check). None when no record carries
    valuation data."""
    vals = [
        (r.get("round"), r["valuation"]) for r in records
        if isinstance(r.get("valuation"), dict)
    ]
    if not vals:
        return None
    last_round, last = vals[-1]
    audits = [
        {"round": rnd, **v["audit"]}
        for rnd, v in vals if isinstance(v.get("audit"), dict)
    ]
    summary: dict = {
        "rounds_reported": len(vals),
        "n_clients": last.get("n_clients"),
        "last_round": last_round,
        "top_clients": last.get("top_clients", []),
        "bottom_clients": last.get("bottom_clients", []),
        "loss_delta_curve": [
            v.get("loss_delta") for _, v in vals
        ],
        "audits": audits,
        "last_audit": audits[-1] if audits else None,
    }
    pc = last.get("per_client")
    if flagged_ids and pc:
        # Flagged-vs-valuation overlay: each detector-flagged client's
        # current value and its rank (0 = most valuable). Rank is over
        # descending value with stable ties.
        ids = pc.get("client_ids", [])
        values = pc.get("value", [])
        by_id = dict(zip(ids, values))
        order = sorted(
            range(len(ids)), key=lambda i: -(values[i] or 0.0)
        )
        rank_of = {ids[i]: r for r, i in enumerate(order)}
        summary["flagged_overlay"] = [
            {
                "id": cid,
                "value": by_id.get(cid),
                "rank": rank_of.get(cid),
            }
            for cid in sorted(flagged_ids)
            if cid in by_id
        ]
    return summary


def summarize_async(records: list[dict]) -> dict | None:
    """Aggregate schema-v4 ``async`` sub-objects into the staleness
    summary: the buffer-occupancy timeline, a histogram over the
    recorded per-round mean staleness, and the simulated-clock speedup
    vs the synchronous wait-for-everyone counterfactual. None when no
    record carries async data."""
    asy = [
        (r.get("round"), r["async"]) for r in records
        if isinstance(r.get("async"), dict)
    ]
    if not asy:
        return None
    occupancy = [
        {"round": rnd, "buffer": a.get("buffer"),
         "applied": bool(a.get("applied"))}
        for rnd, a in asy
    ]
    sim_async = sum(
        a["sim_round_s"] for _, a in asy
        if isinstance(a.get("sim_round_s"), (int, float))
    )
    sim_sync = sum(
        a["sim_round_sync_s"] for _, a in asy
        if isinstance(a.get("sim_round_sync_s"), (int, float))
    )
    staleness = [
        a["mean_staleness"] for _, a in asy
        if isinstance(a.get("mean_staleness"), (int, float))
    ]
    # Integer-bucket histogram over the per-round mean staleness (the
    # records carry round means, not per-upload values — the honest
    # granularity to histogram).
    histogram: dict[str, int] = {}
    for s in staleness:
        histogram[str(int(s))] = histogram.get(str(int(s)), 0) + 1
    clocks = [
        a["sim_clock_s"] for _, a in asy
        if isinstance(a.get("sim_clock_s"), (int, float))
    ]
    return {
        "rounds_reported": len(asy),
        "late_total": sum(a.get("late") or 0 for _, a in asy),
        "on_time_total": sum(a.get("on_time") or 0 for _, a in asy),
        "applied_rounds": sum(1 for o in occupancy if o["applied"]),
        "occupancy_timeline": occupancy,
        "staleness_histogram": dict(
            sorted(histogram.items(), key=lambda kv: int(kv[0]))
        ),
        # Cumulative simulated clock (a resumed run's records carry the
        # carried-over clock, so this can exceed the per-file sums).
        "sim_clock_s": clocks[-1] if clocks else None,
        # THESE FILE'S rounds only — the async/sync pair the speedup
        # ratio is computed from, so the rendered numbers always
        # reproduce the rendered ratio.
        "sim_clock_async_s": round(sim_async, 6),
        "sim_clock_sync_s": round(sim_sync, 6),
        "speedup_vs_sync": (
            round(sim_sync / sim_async, 4) if sim_async > 0 else None
        ),
    }


def summarize_sweep(records: list[dict]) -> dict | None:
    """Aggregate schema-v8 ``sweep`` sub-objects into the sweep summary:
    the per-point accuracy table, the winner, and the compile-reuse
    bookkeeping (which points rode a warm program — the amortization the
    sweep engine exists for). None when no record belongs to a sweep."""
    by_point: dict[int, dict] = {}
    for r in records:
        sw = r.get("sweep")
        if not isinstance(sw, dict):
            continue
        entry = by_point.setdefault(sw["point"], {
            "point": sw["point"],
            "seed": sw.get("seed"),
            "lr": sw.get("lr"),
            "strategy": sw.get("strategy"),
            "group": sw.get("group"),
            "compile_reused": bool(sw.get("compile_reused")),
            "rounds": 0,
            "accuracies": [],
        })
        entry["rounds"] += 1
        if r.get("test_accuracy") is not None:
            entry["accuracies"].append(r["test_accuracy"])
    if not by_point:
        return None
    points = []
    for idx in sorted(by_point):
        e = by_point[idx]
        accs = e.pop("accuracies")
        e["final_accuracy"] = accs[-1] if accs else None
        e["best_accuracy"] = max(accs) if accs else None
        points.append(e)
    scored = [p for p in points if p["final_accuracy"] is not None]
    winner = (
        max(scored, key=lambda p: p["final_accuracy"]) if scored else None
    )
    reused = sum(1 for p in points if p["compile_reused"])
    return {
        "n_points": len(points),
        "strategies": sorted({p["strategy"] for p in points
                              if p["strategy"]}),
        "groups": len({p["group"] for p in points}),
        "rounds_total": sum(p["rounds"] for p in points),
        "compile_reuse_fraction": round(reused / len(points), 4),
        "points": points,
        "winner": (
            {"point": winner["point"], "seed": winner["seed"],
             "lr": winner["lr"],
             "final_accuracy": winner["final_accuracy"]}
            if winner else None
        ),
    }


def summarize_population(records: list[dict]) -> dict | None:
    """Aggregate schema-v9 ``population`` sub-objects into the
    open-world summary: the N-over-time curves, per-round join/depart
    counts, the planted drift cohort, and churn-rejected rounds
    (robustness/population.py). None when no record carries population
    data."""
    pops = [
        (r.get("round"), r["population"]) for r in records
        if isinstance(r.get("population"), dict)
    ]
    if not pops:
        return None
    timeline = [
        {"round": rnd, "n_alive": p.get("n_alive"),
         "n_registered": p.get("n_registered"),
         "joins": p.get("joins", 0), "departs": p.get("departs", 0)}
        for rnd, p in pops
    ]
    first_p = pops[0][1]
    last_p = pops[-1][1]
    # Every record carries the run's startup population; the derivation
    # fallback (first record's post-event count minus its joins) only
    # serves files written before n_initial landed, and is wrong for
    # partial files that don't start at round 0.
    n_initial = first_p.get(
        "n_initial",
        first_p.get("n_registered", 0) - first_p.get("joins", 0),
    )
    drift_ids = sorted({
        int(c) for _, p in pops for c in p.get("drift_clients", [])
    })
    return {
        "rounds_reported": len(pops),
        "n_initial": n_initial,
        "n_registered_final": last_p.get("n_registered"),
        "n_alive_final": last_p.get("n_alive"),
        "joins_total": sum(t["joins"] for t in timeline),
        "departs_total": sum(t["departs"] for t in timeline),
        "growth_ratio": (
            round(last_p["n_registered"] / n_initial, 4)
            if n_initial else None
        ),
        "timeline": timeline,
        "drift_cohort_size": last_p.get("drift_cohort_size", 0),
        "drift_clients": drift_ids,
        "churn_rejected_rounds": [
            rnd for rnd, p in pops if p.get("rejected_by_churn")
        ],
    }


def summarize_spans(records: list[dict]) -> dict | None:
    """Aggregate schema-v12 ``spans`` sub-objects (span_trace='on',
    telemetry/spans.py): run-total span counts and seconds by category,
    the DCN wait-vs-transfer split, and the per-round barrier-skew
    timeline (worst spill/checkpoint skew each round saw). None when no
    record carries span data."""
    sp = [
        (r.get("round"), r["spans"]) for r in records
        if isinstance(r.get("spans"), dict)
    ]
    if not sp:
        return None
    last = sp[-1][1]
    by_cat: dict[str, float] = {}
    for _, s in sp:
        for cat, secs in (s.get("seconds_by_cat") or {}).items():
            by_cat[cat] = by_cat.get(cat, 0.0) + secs
    skew_timeline = [
        {"round": rnd, "spill_skew_ms": s.get("spill_skew_ms"),
         "ckpt_skew_ms": s.get("ckpt_skew_ms")}
        for rnd, s in sp
    ]
    spills = [t["spill_skew_ms"] for t in skew_timeline
              if t["spill_skew_ms"] is not None]
    ckpts = [t["ckpt_skew_ms"] for t in skew_timeline
             if t["ckpt_skew_ms"] is not None]
    return {
        "rounds_reported": len(sp),
        "host_id": last.get("host_id"),
        "hosts": last.get("hosts"),
        "count": sum(int(s.get("count", 0)) for _, s in sp),
        "dropped": sum(int(s.get("dropped", 0)) for _, s in sp),
        "seconds_by_cat": {k: round(v, 6)
                           for k, v in sorted(by_cat.items())},
        "dcn_wait_s": round(
            sum(s.get("dcn_wait_s", 0.0) for _, s in sp), 6),
        "dcn_transfer_s": round(
            sum(s.get("dcn_transfer_s", 0.0) for _, s in sp), 6),
        "spill_skew_ms_max": max(spills) if spills else None,
        "ckpt_skew_ms_max": max(ckpts) if ckpts else None,
        "skew_timeline": skew_timeline,
    }


def summarize_run(records: list[dict], trace_stats: dict | None = None,
                  top_ops: list[dict] | None = None,
                  top_ops_time: list[dict] | None = None,
                  idle_gaps: list[dict] | None = None,
                  costmodel: dict | None = None,
                  span_timeline: dict | None = None) -> dict:
    """Aggregate metrics records into the machine-readable summary the
    terminal renderer and ``--json`` output share."""
    if not records:
        raise ValueError("metrics.jsonl holds no records")
    accs = [r.get("test_accuracy") for r in records]
    secs = [r["round_seconds"] for r in records if "round_seconds" in r]
    best_idx = max(
        range(len(records)),
        key=lambda i: -1.0 if accs[i] is None else accs[i],
    )
    summary: dict = {
        "rounds": len(records),
        "first_round": records[0].get("round"),
        "last_round": records[-1].get("round"),
        "schema_versions": sorted(
            {r.get("schema_version", 1) for r in records}
        ),
        "final_accuracy": accs[-1],
        "best_accuracy": accs[best_idx],
        "best_round": records[best_idx].get("round"),
        "accuracy_curve": accs,
        "round_seconds": {
            "total": sum(secs),
            "mean": statistics.mean(secs) if secs else None,
            "median": statistics.median(secs) if secs else None,
            "max": max(secs) if secs else None,
        },
    }
    rejected = [
        r.get("round") for r in records if r.get("round_rejected")
    ]
    summary["rejected_rounds"] = {"count": len(rejected), "rounds": rejected}

    # --- telemetry sub-objects (schema v2) ----------------------------------
    tels = [(r.get("round"), r["telemetry"]) for r in records
            if isinstance(r.get("telemetry"), dict)]
    if tels:
        phase_tot: dict[str, float] = {}
        per_round_phases = []
        for rnd, tel in tels:
            phases = tel.get("phase_seconds") or {}
            per_round_phases.append({"round": rnd, **phases})
            for name, secs_ in phases.items():
                phase_tot[name] = phase_tot.get(name, 0.0) + secs_
        grand = sum(phase_tot.values()) or 1.0
        summary["phases"] = {
            name: {
                "total_s": round(total, 3),
                "mean_s": round(total / len(tels), 4),
                "share": round(total / grand, 3),
            }
            for name, total in sorted(
                phase_tot.items(), key=lambda kv: -kv[1]
            )
        }
        summary["phase_seconds_per_round"] = per_round_phases

        # Only when the records actually carry per-round compile counts
        # (the threaded oracle's records don't — its compile count is
        # run-scoped in the result dict): a missing key must not render
        # as a fabricated "0 compiles, shape-stable" verdict.
        if any("compiles" in tel for _, tel in tels):
            # Warmup = the first telemetry-carrying record.
            warmup_round = tels[0][0]
            compile_rounds = [
                {"round": rnd, "compiles": tel.get("compiles", 0),
                 "compiled": tel.get("compiled", []),
                 "warmup": rnd == warmup_round}
                for rnd, tel in tels if tel.get("compiles")
            ]
            summary["compiles"] = {
                "total": sum(c["compiles"] for c in compile_rounds),
                "warmup": sum(c["compiles"] for c in compile_rounds
                              if c["warmup"]),
                "post_warmup": sum(c["compiles"] for c in compile_rounds
                                   if not c["warmup"]),
                "rounds": compile_rounds,
            }
        peaks = [tel["peak_hbm_bytes"] for _, tel in tels
                 if tel.get("peak_hbm_bytes")]
        summary["peak_hbm_bytes"] = max(peaks) if peaks else None

    # --- stream sub-objects (schema v5, client_residency='streamed') --------
    streams = [r["stream"] for r in records
               if isinstance(r.get("stream"), dict)]
    if streams:
        h2d_s = sum(s.get("h2d_seconds", 0.0) for s in streams)
        hidden_s = sum(s.get("hidden_seconds", 0.0) for s in streams)
        summary["stream"] = {
            "uploads": len(streams),
            "h2d_bytes": sum(s.get("h2d_bytes", 0) for s in streams),
            "h2d_seconds": round(h2d_s, 4),
            "hidden_seconds": round(hidden_s, 4),
            "overlap_ratio": round(hidden_s / h2d_s, 4) if h2d_s else 0.0,
            "d2h_bytes": sum(s.get("d2h_bytes", 0) for s in streams),
            "d2h_seconds": round(
                sum(s.get("d2h_seconds", 0.0) for s in streams), 4
            ),
        }
        # Cohort-draw replay accounting (participation_sampler,
        # ops/sampling.py): the sampler name + run-total sample time —
        # the host cost the `sample` phase row carries per round.
        samplers = {s["sampler"] for s in streams if s.get("sampler")}
        if samplers:
            summary["stream"]["sampler"] = "/".join(sorted(samplers))
            summary["stream"]["sample_ms"] = round(
                sum(s.get("sample_ms", 0.0) for s in streams), 3
            )

    # --- multihost sub-objects (schema v11, distributed shard store) --------
    mh_summary = summarize_multihost(records)
    if mh_summary is not None:
        summary["multihost"] = mh_summary

    # --- spans sub-objects (schema v12, span_trace='on') --------------------
    spans_summary = summarize_spans(records)
    if spans_summary is not None:
        summary["spans"] = spans_summary
    if span_timeline is not None:
        summary["span_timeline"] = span_timeline

    health = summarize_client_health(records)
    if health is not None:
        summary["client_health"] = health

    # --- valuation sub-objects (schema v7, client_valuation='on') -----------
    flagged_ids: set[int] = set()
    if health is not None:
        for fr in health["flagged_rounds"]:
            flagged_ids.update(int(c) for c in fr["flagged"])
    valuation = summarize_valuation(records, flagged_ids or None)
    if valuation is not None:
        summary["valuation"] = valuation

    async_summary = summarize_async(records)
    if async_summary is not None:
        summary["async_federation"] = async_summary

    # --- sweep sub-objects (schema v8, sweep/engine.py) ---------------------
    sweep_summary = summarize_sweep(records)
    if sweep_summary is not None:
        summary["sweep"] = sweep_summary

    # --- population sub-objects (schema v9, population='dynamic') -----------
    pop_summary = summarize_population(records)
    if pop_summary is not None:
        summary["population"] = pop_summary
        if valuation is not None and pop_summary["drift_clients"]:
            # Drift-cohort overlay on the PR 9 valuation tables: the
            # planted drifting clients SHOULD sink into the bottom-k
            # ranking; one surfacing in the top-k is the surprising
            # disagreement worth a look (the flagged-overlay pattern).
            drift = set(pop_summary["drift_clients"])
            valuation["drift_overlay"] = {
                "drift_in_bottom": [
                    e["id"] for e in valuation["bottom_clients"]
                    if e["id"] in drift
                ],
                "drift_in_top": [
                    e["id"] for e in valuation["top_clients"]
                    if e["id"] in drift
                ],
            }

    # --- costmodel sub-object (schema v6, cost_model_trace) -----------------
    # Explicit costmodel (computed live from --trace) wins; otherwise the
    # LAST record carrying one (the simulator attaches it to the run's
    # final record).
    if costmodel is None:
        cms = [r["costmodel"] for r in records
               if isinstance(r.get("costmodel"), dict)]
        costmodel = cms[-1] if cms else None
    if costmodel is not None:
        summary["costmodel"] = costmodel

    if trace_stats is not None:
        summary["trace"] = trace_stats
    if top_ops is not None:
        summary["top_device_ops"] = top_ops
    if top_ops_time is not None:
        summary["top_device_ops_time"] = top_ops_time
    if idle_gaps is not None:
        summary["device_idle_gaps"] = idle_gaps
    return summary


def summarize_multihost(records: list[dict]) -> dict | None:
    """schema-v11 ``multihost`` sub-objects: the distributed shard
    store's per-host assembly provenance (parallel/streaming
    .DistributedCohortStreamer). The shard-ownership fields are static
    per run (last record wins); spill/DCN traffic accumulates over the
    recorded rounds. None for single-process runs — the off-gate
    rendering convention."""
    mhs = [r["multihost"] for r in records
           if isinstance(r.get("multihost"), dict)]
    if not mhs:
        return None
    last = mhs[-1]
    overlaps = [m["overlap_ratio"] for m in mhs
                if m.get("overlap_ratio") is not None]
    return {
        "hosts": last["hosts"],
        "host_id": last["host_id"],
        "owned_clients": last["owned_clients"],
        "shard_bytes": last["shard_bytes"],
        "rounds_reported": len(mhs),
        "spill_rows": sum(int(m.get("spill_rows", 0)) for m in mhs),
        "dcn_bytes": sum(int(m.get("dcn_bytes", 0)) for m in mhs),
        "mean_overlap_ratio": (
            round(sum(overlaps) / len(overlaps), 4) if overlaps else 0.0
        ),
    }


def render_summary(summary: dict) -> list[str]:
    """Terminal rendering of :func:`summarize_run`'s output."""
    lines = []
    v = "/".join(str(s) for s in summary["schema_versions"])
    lines.append(
        f"run: rounds {summary['first_round']}..{summary['last_round']} "
        f"({summary['rounds']} recorded, metrics schema v{v})"
    )
    if "multihost" in summary:
        # The manifest line of the run header: which host's record
        # stream this artifact dir holds, and its shard of the
        # host-sharded population (per-host checkpoint shards carry the
        # same split — utils/checkpoint.py manifests).
        m = summary["multihost"]
        lines.append(
            f"manifest: {m['hosts']}-host distributed shard store — "
            f"this record stream is host {m['host_id']}, owning "
            f"{m['owned_clients']} clients "
            f"({m['shard_bytes'] / 2**20:.1f} MiB shard)"
        )
    accs = [a for a in summary["accuracy_curve"] if a is not None]
    if accs:
        lines.append(
            f"accuracy: final {summary['final_accuracy']:.4f}, "
            f"best {summary['best_accuracy']:.4f} "
            f"@ round {summary['best_round']}"
        )
        lines.append(f"  curve: {sparkline(accs)}")
    rs = summary["round_seconds"]
    if rs["mean"] is not None:
        lines.append(
            f"round time: total {rs['total']:.2f}s, mean {rs['mean']:.3f}s, "
            f"median {rs['median']:.3f}s, max {rs['max']:.3f}s"
        )
    rej = summary["rejected_rounds"]
    if rej["count"]:
        lines.append(
            f"rejected rounds (quorum): {rej['count']} — {rej['rounds']}"
        )
    else:
        lines.append("rejected rounds (quorum): 0")

    if "phases" in summary:
        lines.append(
            "phase breakdown (per-round mean, share of phased time):"
        )
        for name, st in summary["phases"].items():
            bar = "#" * max(1, int(st["share"] * 40))
            lines.append(
                f"  {name:<12} {st['mean_s']:>9.4f}s  "
                f"{st['share']:>6.1%}  {bar}"
            )
    if "stream" in summary:
        # The host->HBM transfer row (client_residency='streamed'): kept
        # visually with the phase table, but NOT a share of phased time —
        # the prefetch's point is that this time overlaps client_step.
        s = summary["stream"]
        per_upload = s["h2d_seconds"] / max(s["uploads"], 1)
        bar = "#" * max(1, int(s["overlap_ratio"] * 40))
        lines.append(
            f"  {'h2d_stream':<12} {per_upload:>9.4f}s  "
            f"{s['overlap_ratio']:>6.1%} hidden  {bar}"
        )
        lines.append(
            f"  streamed transfers: {s['uploads']} upload(s), "
            f"{s['h2d_bytes'] / 2**20:.1f} MiB h2d"
            + (
                f", {s['d2h_bytes'] / 2**20:.1f} MiB d2h "
                f"({s['d2h_seconds']:.3f}s state writeback)"
                if s["d2h_bytes"] else ""
            )
        )
        if s.get("sampler"):
            lines.append(
                f"  cohort sampler: {s['sampler']} "
                f"({s['sample_ms']:.1f} ms total replay — the `sample` "
                "phase row)"
            )
    if "multihost" in summary:
        # Per-host shard summary (schema v11): this host's share of the
        # owner-sharded assembly — spill is the per-round ownership
        # imbalance, the ONLY client data that crosses DCN.
        m = summary["multihost"]
        lines.append(
            f"  distributed store: host {m['host_id']}/{m['hosts']} "
            f"served {m['rounds_reported']} round(s); spill "
            f"{m['spill_rows']} row(s), "
            f"{m['dcn_bytes'] / 2**20:.2f} MiB over DCN, mean upload "
            f"overlap {m['mean_overlap_ratio']:.1%}"
        )
    if "spans" in summary:
        # Distributed-trace rollup (schema v12): the in-record view —
        # what the spans sub-objects alone say, no journals needed.
        sp = summary["spans"]
        dropped = f", {sp['dropped']} dropped" if sp["dropped"] else ""
        lines.append(
            f"span trace: host {sp['host_id']}/{sp['hosts']}, "
            f"{sp['count']} span(s) over {sp['rounds_reported']} "
            f"round(s){dropped}; DCN wait {sp['dcn_wait_s']:.3f}s vs "
            f"transfer {sp['dcn_transfer_s']:.3f}s"
        )
        skews = []
        if sp["spill_skew_ms_max"] is not None:
            skews.append(f"spill {sp['spill_skew_ms_max']:.3f} ms")
        if sp["ckpt_skew_ms_max"] is not None:
            skews.append(f"checkpoint {sp['ckpt_skew_ms_max']:.3f} ms")
        if skews:
            lines.append(
                f"  worst barrier skew: {', '.join(skews)}"
            )
        spill_curve = [t["spill_skew_ms"] for t in sp["skew_timeline"]
                       if t["spill_skew_ms"] is not None]
        if len(spill_curve) > 1:
            lines.append(
                f"  spill skew/round: {sparkline(spill_curve)}  "
                f"[{min(spill_curve):.2f} .. {max(spill_curve):.2f} ms]"
            )
    if "span_timeline" in summary:
        # Cross-host view stitched from the spans_*.jsonl journals
        # (scripts/trace_timeline.py): barrier skew with the slowest
        # host named, per-host busy/wait split, and the flight-recorder
        # postmortem — the section that answers "which HOST stalled".
        lines.append("distributed trace (stitched span journals):")
        for tl in trace_timeline.render_text(
            summary["span_timeline"]
        ).splitlines():
            lines.append(f"  {tl}")
    if "compiles" in summary:
        c = summary["compiles"]
        lines.append(
            f"XLA compiles: {c['total']} total "
            f"({c['warmup']} warmup, {c['post_warmup']} post-warmup)"
        )
        for cr in c["rounds"]:
            if not cr.get("warmup"):
                names = ", ".join(cr["compiled"]) or "<unknown>"
                lines.append(
                    f"  !! round {cr['round']}: {cr['compiles']} "
                    f"recompile(s) after warmup — {names}"
                )
        if c["post_warmup"] == 0:
            lines.append("  post-warmup recompiles: none (shape-stable run)")
    peak = summary.get("peak_hbm_bytes")
    if peak:
        lines.append(f"peak HBM: {peak / 2**30:.2f} GiB")
    elif "phases" in summary:
        lines.append("peak HBM: unavailable on this backend")

    if "client_health" in summary:
        h = summary["client_health"]
        lines.append(
            f"client health: {h['rounds_reported']} round(s) with stats, "
            f"{h['total_flags']} anomaly flag(s)"
        )
        for fr in h["flagged_rounds"]:
            reasons = ", ".join(
                f"{cid}:{reason}" for cid, reason in fr["reasons"].items()
            )
            lines.append(
                f"  !! round {fr['round']}: flagged {fr['flagged']} "
                f"({reasons})"
            )
        p100 = [
            t["update_norm_p100"] for t in h["divergence_timeline"]
            if t["update_norm_p100"] is not None
        ]
        if p100:
            lines.append(
                f"  divergence timeline (max update norm/round): "
                f"{sparkline(p100)}  "
                f"[{min(p100):.4g} .. {max(p100):.4g}]"
            )
        for key, label in (("quant_mse", "downlink quantization MSE"),
                           ("vote_agreement", "vote agreement")):
            if key in h:
                lines.append(
                    f"  {label}: mean {h[key]['mean']:.6g}, "
                    f"last {h[key]['last']:.6g}"
                )
        loss_series = h.get("per_client_loss") or {}
        if loss_series:
            lines.append("  per-client local loss (round series):")
            for cid in sorted(loss_series, key=int)[:16]:
                series = [v for v in loss_series[cid] if v is not None]
                last = f"{series[-1]:.4f}" if series else "n/a"
                lines.append(
                    f"    client {cid:>4}: {sparkline(series):<12} "
                    f"last {last}"
                )
            if len(loss_series) > 16:
                lines.append(
                    f"    ... {len(loss_series) - 16} more client(s)"
                )

    if "valuation" in summary:
        v = summary["valuation"]
        lines.append(
            f"client valuation: {v['rounds_reported']} round(s) of "
            f"streaming scores over {v['n_clients']} client(s)"
        )
        deltas = [d for d in v["loss_delta_curve"] if d is not None]
        if deltas:
            lines.append(
                f"  loss-delta curve: {sparkline(deltas)}  "
                f"[{min(deltas):+.4g} .. {max(deltas):+.4g}]"
            )

        def _ranked(label, entries):
            if not entries:
                return
            row = ", ".join(
                f"{e['id']}:{e['value']:+.3g}" for e in entries
            )
            lines.append(f"  {label}: {row}")

        _ranked("top clients   ", v["top_clients"])
        _ranked("bottom clients", v["bottom_clients"])
        for o in v.get("flagged_overlay", []):
            # The incentive-side read of the anomaly detector: a flagged
            # client sitting at a HIGH valuation rank is the surprising
            # case worth a look — the two independent signals disagree.
            val = "n/a" if o["value"] is None else f"{o['value']:+.3g}"
            lines.append(
                f"  !! flagged client {o['id']}: valuation {val} "
                f"(rank {o['rank']}/{v['n_clients']}, 0 = most valuable)"
            )
        ov = v.get("drift_overlay")
        if ov:
            # Planted drifting-quality cohort (population='dynamic')
            # against the valuation ranking: sinking into the bottom-k
            # is the expected direction; a drifting client in the top-k
            # is the disagreement worth a look.
            lines.append(
                f"  drift overlay: {len(ov['drift_in_bottom'])}/"
                f"{len(v['bottom_clients'])} of bottom clients are "
                f"planted drifters"
                + (
                    f"; !! drifters in TOP clients: "
                    f"{ov['drift_in_top']}"
                    if ov["drift_in_top"] else ""
                )
            )
        if v["last_audit"] is not None:
            a = v["last_audit"]
            hit = (
                f", memo hit {a['memo_hit_rate']:.0%}"
                if a.get("memo_hit_rate") is not None else ""
            )
            sp = a.get("spearman")
            pe = a.get("pearson")
            # Audit cost face (mesh-sharded GTG): wall seconds + how many
            # devices the walk's subset evaluation partitioned over
            # (absent on pre-v10-era records — rendered only when known).
            secs = a.get("seconds")
            devs = a.get("devices")
            cost = ""
            if secs is not None:
                cost = f", {secs:.1f}s" + (
                    f" on {devs} device(s)" if devs is not None else ""
                )
            lines.append(
                "  GTG audit (round {}): spearman {} pearson {} over {} "
                "permutation(s), converged={}{}{}".format(
                    a["round"],
                    "n/a" if sp is None else f"{sp:.3f}",
                    "n/a" if pe is None else f"{pe:.3f}",
                    a["permutations"], a["converged"], hit, cost,
                )
            )

    if "population" in summary:
        p = summary["population"]
        lines.append(
            f"dynamic population: {p['n_initial']} -> "
            f"{p['n_registered_final']} registered clients "
            f"({p['joins_total']} joined, {p['departs_total']} departed, "
            f"{p['n_alive_final']} alive"
            + (
                f", growth {p['growth_ratio']:.2f}x"
                if p["growth_ratio"] is not None else ""
            )
            + ")"
        )
        alive_curve = [
            t["n_alive"] for t in p["timeline"]
            if t["n_alive"] is not None
        ]
        if alive_curve:
            lines.append(
                f"  alive N over time: {sparkline(alive_curve)}  "
                f"[{min(alive_curve)} .. {max(alive_curve)}]"
            )
        joins = [t["joins"] for t in p["timeline"]]
        departs = [t["departs"] for t in p["timeline"]]
        if any(joins):
            lines.append(
                f"  joins/round:   {sparkline(joins)}  "
                f"(total {sum(joins)})"
            )
        if any(departs):
            lines.append(
                f"  departs/round: {sparkline(departs)}  "
                f"(total {sum(departs)})"
            )
        if p["drift_cohort_size"]:
            ids = p["drift_clients"]
            lines.append(
                f"  planted drift cohort: {p['drift_cohort_size']} "
                "client(s)"
                + (f" {ids}" if ids else "")
            )
        if p["churn_rejected_rounds"]:
            lines.append(
                "  !! rounds rejected by churn (departures pushed "
                f"survivors below quorum): {p['churn_rejected_rounds']}"
            )

    if "async_federation" in summary:
        a = summary["async_federation"]
        lines.append(
            f"async federation: {a['rounds_reported']} round(s), "
            f"{a['late_total']} late / {a['on_time_total']} on-time "
            f"upload(s), buffer applied in {a['applied_rounds']} round(s)"
        )
        occ = [
            o["buffer"] for o in a["occupancy_timeline"]
            if o["buffer"] is not None
        ]
        if occ:
            lines.append(
                f"  buffer occupancy/round: {sparkline(occ)}  "
                f"[{min(occ)} .. {max(occ)}]"
            )
        if a["staleness_histogram"]:
            total = sum(a["staleness_histogram"].values())
            lines.append("  staleness histogram (round means):")
            for bucket, count in a["staleness_histogram"].items():
                bar = "#" * max(1, int(count / total * 40))
                lines.append(f"    s={bucket:>3}: {count:>4}  {bar}")
        if a["speedup_vs_sync"] is not None:
            # Per-file sums on both sides: the printed pair reproduces
            # the printed ratio even on resumed runs, whose cumulative
            # sim_clock_s exceeds this file's rounds.
            lines.append(
                f"  simulated clock: {a['sim_clock_async_s']:.1f}s async "
                f"vs {a['sim_clock_sync_s']:.1f}s sync — "
                f"{a['speedup_vs_sync']:.2f}x speedup"
            )
    if "sweep" in summary:
        sw = summary["sweep"]
        strategies = "/".join(sw["strategies"]) or "?"
        lines.append(
            f"sweep: {sw['n_points']} point(s), strategy {strategies}, "
            f"{sw['groups']} config-hash group(s), "
            f"{sw['rounds_total']} experiment-rounds"
        )
        lines.append(
            f"  compile reuse: {sw['compile_reuse_fraction']:.0%} of "
            "points rode a warm program"
        )
        lines.append("  point  seed        lr  warm  final acc  best acc")
        for p in sw["points"]:
            fin = (
                f"{p['final_accuracy']:.4f}"
                if p["final_accuracy"] is not None else "n/a"
            )
            best = (
                f"{p['best_accuracy']:.4f}"
                if p["best_accuracy"] is not None else "n/a"
            )
            lr = f"{p['lr']:.4g}" if p["lr"] is not None else "?"
            warm = "yes" if p["compile_reused"] else "no"
            lines.append(
                f"  {p['point']:>5}  {p['seed']!s:>4}  {lr:>8}  "
                f"{warm:>4}  {fin:>9}  {best:>8}"
            )
        if sw["winner"] is not None:
            w = sw["winner"]
            lines.append(
                f"  winner: point {w['point']} (seed {w['seed']}, "
                f"lr {w['lr']:.4g}) at {w['final_accuracy']:.4f}"
            )
        cm = summary.get("costmodel")
        if cm is not None and cm.get("per_topology"):
            # $/sweep (telemetry/costmodel.py pricing discipline): the
            # compiled program priced once, multiplied by the sweep's
            # experiment-round occupancy — per topology-table entry.
            lines.append(
                f"  $/sweep ({sw['rounds_total']} experiment-rounds):"
            )
            for name, t in cm["per_topology"].items():
                usd = t.get("usd_per_round")
                if usd is None:
                    continue
                lines.append(
                    f"    {name:<10} ${usd * sw['rounds_total']:.4f}"
                    f"  (x{t['chips']} chips, "
                    f"{t['predicted_ms']:.1f} ms/round predicted)"
                )
    if "costmodel" in summary:
        # "What would this cost at scale": the roofline prediction per
        # topology-table entry, measured run as the anchor row.
        cm = summary["costmodel"]
        run_rounds = cm.get("run_rounds")
        horizon = f" @ {run_rounds} rounds" if run_rounds else ""
        lines.append(
            f"cost at scale (roofline on the traced ledger; "
            f"anchor {cm['anchor_topology']}{horizon}):"
        )
        if cm.get("measured_ms") is not None:
            lines.append(
                f"  measured   {cm['anchor_topology']:<10} "
                f"round {cm['measured_ms']:>10.1f} ms  (this run — "
                f"anchor)"
            )
        for name, t in (cm.get("per_topology") or {}).items():
            usd_run = t.get("usd_per_run")
            cost = (
                f"  ${usd_run:.2f}/run" if usd_run is not None else
                f"  ${t.get('usd_per_round', 0):.6f}/round"
            )
            lines.append(
                f"  predicted  {name:<10} "
                f"round {t['predicted_ms']:>10.1f} ms  x{t['chips']:<4}"
                f"{t.get('bottleneck', '?')}-bound{cost}"
            )
        if cm.get("model_error_ratio") is not None:
            lines.append(
                f"  model error: predicted/measured = "
                f"{cm['model_error_ratio']:.3f} "
                f"(band gated by compare_bench --model-drift-threshold)"
            )
        cats = cm.get("categories") or {}
        if cats:
            lines.append("  per-category roofline (per round, anchor):")
            for cat, c in sorted(
                cats.items(), key=lambda kv: -kv[1]["predicted_ms"]
            ):
                lines.append(
                    f"    {cat:<12} {c['predicted_ms']:>9.2f} ms "
                    f"predicted  {c['bytes_gb']:>8.2f} GB  "
                    f"{c.get('bottleneck', '?')}-bound"
                )
    if "trace" in summary:
        t = summary["trace"]
        lines.append(
            f"device trace: {t['device_ms']:.1f} ms device time, "
            f"{t['bytes_gb']:.3f} GB accessed, {t['op_count']} ops"
        )
    if summary.get("top_device_ops"):
        lines.append("top device ops by bytes:")
    for op in summary.get("top_device_ops", []):
        lines.append(
            f"  {op['bytes_gb']:>8.3f} GB  {op['device_ms']:>8.2f} ms  "
            f"x{op['count']:<5} {op['name']}"
        )
    if summary.get("top_device_ops_time"):
        lines.append("top device ops by time:")
    for op in summary.get("top_device_ops_time", []):
        lines.append(
            f"  {op['device_ms']:>8.2f} ms  {op['bytes_gb']:>8.3f} GB  "
            f"x{op['count']:<5} {op['name']}"
        )
    if summary.get("device_idle_gaps"):
        # utils/tracing.attribute_idle_gaps: each gap between two
        # executed programs, named by the innermost host span
        # (telemetry/spans.py) covering its midpoint.
        lines.append("device idle gaps by host span:")
    for gap in summary.get("device_idle_gaps", []):
        lines.append(
            f"  {1e3 * gap['seconds']:>8.3f} ms  x{gap['count']:<5} "
            f"{gap['span']:<14} {gap['between']}"
        )
    return lines


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="Render a run's artifacts dir into a telemetry summary"
    )
    ap.add_argument("artifacts",
                    help="run artifacts dir or metrics.jsonl path")
    ap.add_argument("--trace", default=None,
                    help="jax.profiler trace dir (config.profile_dir)")
    ap.add_argument("--json", default=None,
                    help="also write the summary as JSON to this path")
    ap.add_argument("--top", type=int, default=10,
                    help="top-K device ops from --trace (default 10)")
    ap.add_argument("--trace-rounds", type=int, default=1,
                    help="rounds the --trace covers (per-round basis of "
                         "the cost model; default 1)")
    ap.add_argument("--cost-topology", default=None,
                    help="topology-table anchor for the --trace cost "
                         "model (default: costmodel.DEFAULT_ANCHOR)")
    ap.add_argument("--cost-rounds", type=int, default=None,
                    help="run horizon for the $/run projection (default: "
                         "this run's recorded round count)")
    ap.add_argument("--spans", default=None,
                    help="directory holding spans_*.jsonl host journals "
                         "(default: the artifacts dir itself)")
    ap.add_argument("--host", type=int, default=None,
                    help="restrict the distributed-trace section to one "
                         "host id")
    args = ap.parse_args(argv)

    try:
        records = load_metrics(args.artifacts)
        span_timeline = None
        span_dir = args.spans or (
            args.artifacts if os.path.isdir(args.artifacts)
            else os.path.dirname(args.artifacts)
        )
        journal_paths = trace_timeline.find_journals([span_dir]) \
            if os.path.isdir(span_dir) else []
        if journal_paths:
            span_timeline = trace_timeline.summarize(
                [trace_timeline.load_journal(p) for p in journal_paths],
                host=args.host,
            )
        trace_stats = top_ops = top_ops_time = costmodel = None
        idle_gaps = None
        if args.trace:
            # Deferred: utils.tracing imports jax. One gzip pass serves
            # the totals and both rankings; a second builds the cost
            # model's categorized ledger.
            from distributed_learning_simulator_tpu.telemetry.costmodel import (  # noqa: E501
                DEFAULT_ANCHOR,
                costmodel_record,
                ledger_totals,
            )
            from distributed_learning_simulator_tpu.utils.tracing import (
                attribute_idle_gaps,
                categorize_ops,
                device_op_report,
            )

            report = device_op_report(args.trace, k=args.top)
            trace_stats = report["totals"]
            top_ops = report["by_bytes"]
            top_ops_time = report["by_time"]
            idle_gaps = attribute_idle_gaps(args.trace)[:args.top]
            ledger = categorize_ops(args.trace)
            if ledger and ledger_totals(ledger)["bytes_gb"] > 0:
                # Anchor on this run's measured steady rounds (round 0
                # carries compile when more than one record exists).
                secs = [r["round_seconds"] for r in records
                        if "round_seconds" in r]
                steady = secs[1:] or secs
                costmodel = costmodel_record(
                    ledger,
                    trace_rounds=args.trace_rounds,
                    anchor=args.cost_topology or DEFAULT_ANCHOR,
                    measured_ms=(
                        1e3 * statistics.median(steady) if steady else None
                    ),
                    run_rounds=args.cost_rounds or len(records),
                )
        summary = summarize_run(records, trace_stats=trace_stats,
                                top_ops=top_ops, top_ops_time=top_ops_time,
                                idle_gaps=idle_gaps, costmodel=costmodel,
                                span_timeline=span_timeline)
    except (FileNotFoundError, ValueError) as e:
        print(str(e), file=sys.stderr)
        return 2
    for line in render_summary(summary):
        print(line)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=2)
        print(f"summary JSON: {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
