"""Diff two bench JSON records with a relative-threshold regression gate.

The BENCH_r*.json trajectory used to be eyeball-only: a reviewer had to
hand-diff nested JSON to notice a lost fusion or a slower flagship leg.
This tool makes it a gate:

    python scripts/compare_bench.py OLD.json NEW.json [--threshold 0.05]
        [--force] [--json]

* Exit 0 — no tracked metric regressed beyond the threshold.
* Exit 1 — regression(s): any tracked metric moved in its BAD direction
  by more than ``--threshold`` (relative). Each is printed with both
  values and the relative change.
* Exit 2 — the runs are not comparable (``config_hash`` mismatch — the
  program-defining knobs differ — or bench ``schema_version`` mismatch)
  and ``--force`` was not given. Records predating the provenance stamp
  (no ``schema_version``/``config_hash``) compare value-by-value with a
  warning; the gate cannot prove comparability for them.

Tracked metrics (missing on either side -> skipped, listed as such):
headline/flagship rates (higher is better), converged-GTG round seconds
(lower), the deterministic traced-bytes proxies (lower — these are
byte-exact program properties, so ANY growth beyond the threshold is a
real program change), rejected-round and survivor robustness counters.

Two in-record gates run on the NEW record alone: its ``client_stats``
sub-object already holds the on-vs-off round-time overhead measured
within that single bench run (bench.py re-runs the headline program
with client_stats='on'), so an overhead above
``--stats-overhead-threshold`` is a regression regardless of the old
record — the feature's promise is "cheap enough to leave on". The
ratio is judged ABSOLUTELY, never as a tracked relative metric: it
hovers near zero, where relative changes are pure noise. The ``async``
leg's ``async_speedup_ratio`` (simulated-clock speedup of deadline
rounds over the sync counterfactual) gets the same treatment:
``--async-speedup-threshold`` is an absolute floor, default 1.0. And
the ``stream`` leg's prefetch ``overlap_ratio`` (fraction of host->HBM upload time hidden behind
compute at the largest swept population, client_residency='streamed'):
``--stream-overlap-threshold`` is an absolute floor, default 0.5 —
and the same leg's ``cohort_rate`` (steady cohort·rounds/s at that
population under the fastest-supported ``participation_sampler``)
gets ``--stream-cohort-rate-threshold`` as an absolute floor, default
900: the O(cohort) hashed sampler retired the exact replay's ~1 s/round
host-bound ceiling (328 c·r/s at N=1e6, r07), and the gate keeps the
million-client leg model-bound. The
``valuation`` leg's ``audit_spearman`` (streaming client-valuation
vector vs cumulative exact-GTG audit SVs on the graded-quality
differential config, telemetry/valuation.py) gets
``--valuation-corr-threshold`` as an absolute floor, default 0.8 —
the cheap estimator must keep tracking exact Shapley. The ``sweep``
leg's ``sweep_amortization_ratio`` (serial-solo vs vmapped-fleet wall
for the same points, sweep/engine.py) gets
``--sweep-amortization-threshold`` as an absolute floor, default 2.0 —
the fleet must at least halve the sweep's wall-clock (compile paid
once is the whole multiplier). The ``churn`` leg's
``churn_overhead_ratio`` (10x population-growth dynamic run vs the
same program static, robustness/population.py) gets
``--churn-overhead-threshold`` as an absolute ceiling, default 0.10 —
the registration stream must ride the round at marginal cost, never
relatively tracked. The
``gtg`` leg's ``gtg_scaling_ratio`` (D=2/D=1 subset-eval throughput of
the mesh-sharded GTG walk's scaling microbench, algorithms/shapley.py)
gets ``--gtg-scaling-threshold`` as an absolute floor, default 1.5 —
two devices must buy at least half a device's worth of extra walk
throughput, never relatively tracked; bench arms the key only when the
host could honestly measure it (>= 2 usable cores — a 1-core cgroup
cannot overlap two devices' compute, and the unarmed measurement stays
in the record under ``gtg.scaling``). The ``mhost`` leg's
``mhost_cohort_rate`` (steady cohort-rounds/s of the 2-process
distributed-shard-store N-sweep at its largest population,
parallel/streaming.DistributedCohortStreamer) gets
``--mhost-cohort-rate-threshold`` as an absolute floor, default 200 —
the owner-sharded data plane (cohort assembly + spill exchange +
per-host placement) must keep the composed streamed x multihost run
off the host-bound floor, never relatively tracked; armed like the gtg
gate only on hosts with >= 2 usable cores (a 1-core cgroup cannot
overlap two processes' compute — the honest number stays unarmed
under ``mhost.cohort_rate``). The ``spans`` leg's ``overhead_ratio``
(headline re-run with ``span_trace='on'``, telemetry/spans.py) gets
``--span-overhead-threshold`` as an absolute ceiling, default 0.05 —
the distributed tracer's promise is "cheap enough to leave on", and
like the client-stats overhead the near-zero ratio is never relatively
tracked. The
``costmodel`` leg's ``model_error_ratio`` per program (predicted /
measured per-round ms from the roofline model, telemetry/costmodel.py)
is judged as an absolute BAND around 1.0 (``--model-drift-threshold``,
default 0.35 — wide enough for the documented ~25% device-vs-wall
host-side share on the cnn headline, docs/PERFORMANCE.md § Predicted
pod-scale cost): a prediction drifting out of band means the program
changed character faster than the fitted model — refit deliberately
(docs update) instead of letting capacity plans rot silently.

Deliberately imports nothing heavy (no jax): usable as a CI gate and
fast enough to self-test in tier-1 (tests/test_compare_bench.py).
"""

from __future__ import annotations

import argparse
import json
import sys

# (dotted path, direction, description). Direction is the GOOD direction;
# a relative move against it beyond the threshold is a regression.
TRACKED = [
    ("value", "higher", "headline median clients*rounds/s"),
    ("mean_rate", "higher", "headline mean clients*rounds/s"),
    ("flagship.value", "higher", "flagship median clients*rounds/s"),
    ("gtg.value", "lower", "converged-GTG round seconds"),
    ("proxy.traced_bytes_gb", "lower", "cnn traced bytes proxy (GB)"),
    ("proxy.traced_op_count", "lower", "cnn traced op count"),
    ("proxy_flagship.traced_bytes_gb", "lower",
     "flagship traced bytes proxy (GB)"),
    ("proxy_flagship.traced_op_count", "lower", "flagship traced op count"),
    ("robustness.rounds_rejected", "lower", "quorum-rejected rounds"),
    ("robustness.mean_survivor_count", "higher", "mean survivor count"),
    # client_stats.overhead_ratio is deliberately NOT tracked here: it is
    # the difference of two noisy medians hovering near zero, so a
    # relative-change gate on it would flap (0.01 -> 0.02 reads as
    # +100%). The absolute in-record gate (overhead_gate) is the designed
    # mechanism. costmodel.*.model_error_ratio follows the same rule
    # (near-1.0 ratios must never be tracked relatively — PR 4/5
    # precedent): the absolute band gate (model_drift_gate) judges it.
]


def get_path(record: dict, dotted: str):
    """Resolve a dotted path; None when any hop is missing/non-numeric."""
    node = record
    for part in dotted.split("."):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    return node if isinstance(node, (int, float)) and not isinstance(
        node, bool
    ) else None


def check_comparable(old: dict, new: dict) -> str | None:
    """Reason the two records must NOT be gate-compared, or None if OK.

    Refusal needs the stamp on BOTH sides: pre-provenance records (bench
    schema v1, no stamp) can't prove incomparability, so they pass with
    the caveat printed by main().
    """
    o_v, n_v = old.get("schema_version"), new.get("schema_version")
    if o_v is not None and n_v is not None and o_v != n_v:
        return f"bench schema_version differs: {o_v} vs {n_v}"
    o_h, n_h = old.get("config_hash"), new.get("config_hash")
    if o_h is not None and n_h is not None and o_h != n_h:
        return (
            f"config_hash differs: {o_h} vs {n_h} — the runs measured "
            "different programs (model/population/chunk/dtype/failure "
            "knobs); re-run one side or pass --force"
        )
    return None


def compare_records(old: dict, new: dict, threshold: float = 0.05) -> dict:
    """Pure comparison: returns ``{"regressions", "improvements",
    "unchanged", "skipped"}`` lists of per-metric dicts."""
    out = {"regressions": [], "improvements": [], "unchanged": [],
           "skipped": []}
    for dotted, direction, desc in TRACKED:
        o, n = get_path(old, dotted), get_path(new, dotted)
        if o is None or n is None:
            out["skipped"].append({"metric": dotted, "description": desc})
            continue
        if o == 0:
            # Relative change undefined; only an absolute move in the bad
            # direction counts (covers counters like rounds_rejected=0).
            bad = (n > 0) if direction == "lower" else (n < 0)
            rel = None
        else:
            rel = (n - o) / abs(o)
            worse = -rel if direction == "higher" else rel
            bad = worse > threshold
        entry = {
            "metric": dotted, "description": desc, "old": o, "new": n,
            "relative_change": rel, "direction": direction,
        }
        if bad:
            out["regressions"].append(entry)
        elif rel is not None and abs(rel) > threshold:
            out["improvements"].append(entry)
        else:
            out["unchanged"].append(entry)
    return out


def overhead_gate(record: dict, threshold: float) -> dict | None:
    """In-record client-stats overhead gate (see module docstring): the
    regression entry when the record's own measured ``client_stats=on``
    overhead exceeds ``threshold``, else None (absent leg included)."""
    ratio = get_path(record, "client_stats.overhead_ratio")
    if ratio is None or ratio <= threshold:
        return None
    return {
        "metric": "client_stats.overhead_ratio",
        "description": (
            "client_stats=on round-time overhead vs the same run's "
            "off-mode headline"
        ),
        "old": threshold, "new": ratio,
        "relative_change": None, "direction": "lower",
    }


def async_speedup_gate(record: dict, threshold: float) -> dict | None:
    """In-record async-federation gate: bench.py's ``async`` leg records
    the run's simulated-clock speedup of deadline rounds over the
    wait-for-everyone synchronous counterfactual
    (``async_speedup_ratio``, computed from the same arrival draws —
    a deterministic program property). A ratio below ``threshold``
    means deadline rounds stopped beating sync under the documented
    80/20 population — a regression regardless of the old record.
    Judged ABSOLUTELY like the other in-record gates (near a fixed
    operating point, a relative gate would flap). None when the leg is
    absent or the floor holds."""
    ratio = get_path(record, "async.async_speedup_ratio")
    if ratio is None or ratio >= threshold:
        return None
    return {
        "metric": "async.async_speedup_ratio",
        "description": (
            "simulated-clock speedup of async deadline rounds vs the "
            "sync wait-for-everyone counterfactual (>= 1.0 means async "
            "pays)"
        ),
        "old": threshold, "new": ratio,
        "relative_change": None, "direction": "higher",
    }


def stream_overlap_gate(record: dict, threshold: float) -> dict | None:
    """In-record streamed-residency gate: bench.py's ``stream`` leg
    records, at its largest swept population, the fraction of host->HBM
    cohort-upload time the double-buffered prefetch hid behind compute
    (``overlap_ratio``, parallel/streaming.py). A ratio below
    ``threshold`` means the prefetch stopped overlapping — per-dispatch
    transfers have gone synchronous and the streamed mode's cost model
    no longer holds. Judged ABSOLUTELY like the other in-record gates
    (the ratio sits near a fixed operating point, where a relative gate
    would flap). None when the leg is absent or the floor holds."""
    ratio = get_path(record, "stream.overlap_ratio")
    if ratio is None or ratio >= threshold:
        return None
    return {
        "metric": "stream.overlap_ratio",
        "description": (
            "fraction of streamed-residency host->HBM upload time hidden "
            "behind compute at the largest swept population (prefetch "
            "must overlap)"
        ),
        "old": threshold, "new": ratio,
        "relative_change": None, "direction": "higher",
    }


def stream_cohort_rate_gate(record: dict, threshold: float) -> dict | None:
    """In-record streamed-throughput gate: bench.py's ``stream`` leg
    records, at its largest swept population under the
    fastest-supported ``participation_sampler`` (hashed when swept —
    ops/sampling.py), the steady cohort training rate
    (``cohort_rate``, cohort·rounds/s). A rate below ``threshold``
    means the million-client stream leg went host-bound again — the
    regression the O(cohort) sampler exists to prevent (the exact
    replay's O(N log N) draw measured ~1 s/round at N=1e6,
    docs/PERFORMANCE.md § Streamed client state). Judged ABSOLUTELY
    like the other in-record gates (an absolute floor in the record's
    own units, the PR 4/5/7 precedent). None when the leg is absent or
    the floor holds."""
    rate = get_path(record, "stream.cohort_rate")
    if rate is None or rate >= threshold:
        return None
    return {
        "metric": "stream.cohort_rate",
        "description": (
            "steady cohort·rounds/s of the streamed-residency leg at "
            "its largest swept population, fastest-supported sampler "
            "(the million-client leg must stay model-bound, not "
            "host-bound on the cohort draw)"
        ),
        "old": threshold, "new": rate,
        "relative_change": None, "direction": "higher",
    }


def valuation_corr_gate(record: dict, threshold: float) -> dict | None:
    """In-record valuation-fidelity gate: bench.py's ``valuation`` leg
    measures, on the small-N graded-quality differential config, the
    Spearman correlation between the streaming client-valuation vector
    and the cumulative truncated-GTG audit SVs
    (telemetry/valuation.py). A correlation below ``threshold`` means
    the cheap always-on estimator stopped tracking exact Shapley — its
    per-round signal is no longer a trustworthy contribution ranking —
    a regression regardless of the old record. Judged ABSOLUTELY (the
    PR 4/5/8 precedent: the correlation sits near a fixed operating
    point ~0.85-0.9, where a relative gate would flap). None when the
    leg is absent or the floor holds."""
    corr = get_path(record, "valuation.audit_spearman")
    if corr is None or corr >= threshold:
        return None
    return {
        "metric": "valuation.audit_spearman",
        "description": (
            "Spearman correlation of the streaming client-valuation "
            "vector vs cumulative exact GTG audit SVs on the "
            "graded-quality differential (estimator fidelity floor)"
        ),
        "old": threshold, "new": corr,
        "relative_change": None, "direction": "higher",
    }


def sweep_amortization_gate(record: dict, threshold: float) -> dict | None:
    """In-record sweep-engine gate: bench.py's ``sweep`` leg measures,
    within one bench run, the wall-clock of N serial solo runs against
    the same N points executed as one vmapped seed fleet
    (``sweep_amortization_ratio`` = serial wall / fleet wall; the fleet
    pays one compile and one dispatch per round for every experiment).
    A ratio below ``threshold`` means the fleet stopped amortizing —
    compile or dispatch overhead is being re-paid per point — a
    regression regardless of the old record. Judged ABSOLUTELY like the
    other in-record gates (the ratio sits at a fixed operating point set
    by the compile/run balance, where a relative gate would flap; the
    PR 4/5/10 precedent). None when the leg is absent or the floor
    holds."""
    ratio = get_path(record, "sweep.sweep_amortization_ratio")
    if ratio is None or ratio >= threshold:
        return None
    return {
        "metric": "sweep.sweep_amortization_ratio",
        "description": (
            "serial-solo vs vmapped-fleet wall-clock ratio for the "
            "same sweep points (>= 2.0 means the fleet at least halves "
            "the sweep's wall — the acceptance operating point)"
        ),
        "old": threshold, "new": ratio,
        "relative_change": None, "direction": "higher",
    }


def gtg_scaling_gate(record: dict, threshold: float) -> dict | None:
    """In-record GTG mesh-scaling gate: bench.py's ``gtg`` leg runs a
    D=2-vs-D=1 subset-eval throughput microbench through the real
    ``_SubsetEvaluator`` (the mesh-sharded GTG walk's fused-call shape,
    algorithms/shapley.py) and records ``gtg_scaling_ratio`` — ONLY when
    the host had >= 2 usable cores, so the number is an honest
    device-parallel measurement. A ratio below ``threshold`` means
    sharding the walk stopped paying (lost replication short-circuit,
    accidental collective, per-call placement cost) — a regression
    regardless of the old record. Judged ABSOLUTELY (the PR 4/5/8 gate
    precedent: the ratio sits near a fixed operating point where a
    relative gate would flap). None when the leg is absent (including
    the unarmed 1-core case) or the floor holds."""
    ratio = get_path(record, "gtg.gtg_scaling_ratio")
    if ratio is None or ratio >= threshold:
        return None
    return {
        "metric": "gtg.gtg_scaling_ratio",
        "description": (
            "D=2/D=1 subset-eval throughput of the mesh-sharded GTG "
            "walk (two devices must keep buying walk throughput)"
        ),
        "old": threshold, "new": ratio,
        "relative_change": None, "direction": "higher",
    }


def mhost_cohort_rate_gate(record: dict, threshold: float) -> dict | None:
    """In-record multihost stream-throughput gate: bench.py's ``mhost``
    leg runs the 2-process distributed-shard-store N-sweep (streamed +
    hashed cohorts, owner-sharded assembly with the spill exchange —
    parallel/streaming.DistributedCohortStreamer) and records
    ``mhost_cohort_rate`` (cohort·rounds/s at the largest population) —
    ONLY when the host had >= 2 usable cores, so the two processes'
    compute genuinely overlaps (the PR 14 arming precedent: a 1-core
    cgroup records the honest number under ``cohort_rate`` unarmed). A
    rate below ``threshold`` means the distributed data plane stopped
    keeping the composed run model-bound (exchange on the critical
    path, lost prefetch overlap, per-round placement cost) — a
    regression regardless of the old record. Judged ABSOLUTELY as an
    in-record floor; None when the leg is absent (including unarmed) or
    the floor holds."""
    rate = get_path(record, "mhost.mhost_cohort_rate")
    if rate is None or rate >= threshold:
        return None
    return {
        "metric": "mhost.mhost_cohort_rate",
        "description": (
            "steady cohort-rounds/s of the 2-process distributed "
            "shard store at the largest swept population (the "
            "owner-sharded data plane must stay off the critical path)"
        ),
        "old": threshold, "new": rate,
        "relative_change": None, "direction": "higher",
    }


def churn_overhead_gate(record: dict, threshold: float) -> dict | None:
    """In-record open-world-churn gate: bench.py's ``churn`` leg runs a
    10x population-growth ``population='dynamic'`` run against the same
    program static (both streamed + hashed + sampled — the composition
    dynamic populations require) and records ``churn_overhead_ratio``,
    the dynamic-vs-static median round-time ratio minus one
    (robustness/population.py). A ratio above ``threshold`` means the
    registration stream (masked draw, event draws, store growth, drift
    mutation, synchronous gather) stopped riding the round at marginal
    cost — a regression regardless of the old record. Judged ABSOLUTELY
    (the PR 4 overhead-gate precedent: the ratio sits near a fixed small
    operating point, where a relative gate would flap). None when the
    leg is absent or the ceiling holds."""
    ratio = get_path(record, "churn.churn_overhead_ratio")
    if ratio is None or ratio <= threshold:
        return None
    return {
        "metric": "churn.churn_overhead_ratio",
        "description": (
            "round-time overhead of the 10x-growth dynamic-population "
            "run vs the same program static (registration stream must "
            "ride the round at marginal cost)"
        ),
        "old": threshold, "new": ratio,
        "relative_change": None, "direction": "lower",
    }


def span_overhead_gate(record: dict, threshold: float) -> dict | None:
    """In-record span-trace overhead gate: bench.py's ``spans`` leg
    re-runs the headline program with ``span_trace='on'``
    (telemetry/spans.py) and records the on-vs-off round-time
    ``overhead_ratio`` within that single bench run. A ratio above
    ``threshold`` means the recorder stopped being cheap enough to leave
    on in production — a regression regardless of the old record.
    Judged ABSOLUTELY (the PR 4/5 precedent: the ratio hovers near
    zero, where relative changes are pure noise). None when the leg is
    absent or the ceiling holds."""
    ratio = get_path(record, "spans.overhead_ratio")
    if ratio is None or ratio <= threshold:
        return None
    return {
        "metric": "spans.overhead_ratio",
        "description": (
            "span_trace=on round-time overhead vs the same run's "
            "off-mode headline (the distributed tracer must stay cheap "
            "enough to leave on)"
        ),
        "old": threshold, "new": ratio,
        "relative_change": None, "direction": "lower",
    }


def model_drift_gate(record: dict, threshold: float) -> list[dict]:
    """In-record cost-model drift gate: bench.py's ``costmodel`` leg
    records, per proxied program, the roofline model's predicted-vs-
    measured per-round ratio (``model_error_ratio``,
    telemetry/costmodel.py). A ratio outside the absolute band
    ``1.0 +- threshold`` means the analytic model no longer describes
    the program it prices — capacity projections built on it are stale
    and the efficiency factors need a deliberate, documented refit
    (docs/PERFORMANCE.md § Predicted pod-scale cost). Judged as an
    absolute BAND, never relatively (the ratio sits near a fixed
    operating point, where a relative gate would flap); returns one
    regression entry per out-of-band program, empty when the leg is
    absent or every ratio holds."""
    out = []
    for program in ("cnn", "flagship"):
        ratio = get_path(record, f"costmodel.{program}.model_error_ratio")
        if ratio is None or abs(ratio - 1.0) <= threshold:
            continue
        out.append({
            "metric": f"costmodel.{program}.model_error_ratio",
            "description": (
                f"roofline-predicted vs measured per-round time of the "
                f"{program} program (must stay within 1.0 +- "
                f"{threshold:g}; refit the model deliberately, see "
                "docs/PERFORMANCE.md)"
            ),
            "old": threshold, "new": ratio,
            "relative_change": None, "direction": "near-1.0",
        })
    return out


def _fmt(entry: dict) -> str:
    rel = entry["relative_change"]
    rel_s = f"{rel:+.1%}" if rel is not None else "n/a"
    return (
        f"  {entry['metric']:<34} {entry['old']:>12g} -> "
        f"{entry['new']:>12g}  ({rel_s}, {entry['direction']} is better) "
        f"— {entry['description']}"
    )


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="Regression gate over two bench.py JSON records"
    )
    ap.add_argument("old", help="baseline bench JSON file")
    ap.add_argument("new", help="candidate bench JSON file")
    ap.add_argument("--threshold", type=float, default=0.05,
                    help="relative regression tolerance (default 0.05)")
    ap.add_argument("--force", action="store_true",
                    help="compare even when provenance says incomparable")
    ap.add_argument("--stats-overhead-threshold", type=float, default=0.10,
                    help="max tolerated client_stats=on round-time overhead "
                         "ratio in the NEW record (default 0.10)")
    ap.add_argument("--async-speedup-threshold", type=float, default=1.0,
                    help="min tolerated simulated-clock speedup in the "
                         "NEW record's async leg (default 1.0 — deadline "
                         "rounds must at least match the synchronous "
                         "counterfactual; the ratio is deterministic, "
                         "not wall-clock noise)")
    ap.add_argument("--stream-overlap-threshold", type=float, default=0.5,
                    help="min tolerated prefetch overlap ratio in the NEW "
                         "record's stream leg at its largest population "
                         "(default 0.5 — at least half the host->HBM "
                         "upload time must hide behind compute)")
    ap.add_argument("--stream-cohort-rate-threshold", type=float,
                    default=900.0,
                    help="min tolerated cohort*rounds/s in the NEW "
                         "record's stream leg at its largest population, "
                         "fastest-supported sampler (default 900 — ~3x "
                         "the r07 host-bound 328 c*r/s N=1e6 CPU "
                         "baseline the hashed sampler retired; "
                         "docs/PERFORMANCE.md § Streamed client state)")
    ap.add_argument("--sweep-amortization-threshold", type=float,
                    default=2.0,
                    help="min tolerated serial-vs-fleet wall ratio in the "
                         "NEW record's sweep leg (default 2.0 — an "
                         "8-point vmapped seed fleet must finish in under "
                         "half the wall of 8 serial solo runs; compile "
                         "paid once is the multiplier)")
    ap.add_argument("--valuation-corr-threshold", type=float, default=0.8,
                    help="min tolerated streaming-valuation vs GTG-audit "
                         "Spearman correlation in the NEW record's "
                         "valuation leg (default 0.8 — the estimator "
                         "must keep tracking exact Shapley on the "
                         "differential config; measured operating point "
                         "~0.85-0.9)")
    ap.add_argument("--gtg-scaling-threshold", type=float, default=1.5,
                    help="min tolerated D=2/D=1 subset-eval throughput "
                         "ratio in the NEW record's gtg leg (default 1.5 "
                         "— sharding the GTG walk over two devices must "
                         "buy at least 1.5x; bench records the key only "
                         "on hosts that can honestly measure it, i.e. "
                         ">= 2 usable cores)")
    ap.add_argument("--mhost-cohort-rate-threshold", type=float,
                    default=200.0,
                    help="min tolerated steady cohort-rounds/s in the NEW "
                         "record's mhost leg at its largest population "
                         "(default 200 — the 2-process distributed shard "
                         "store must keep the composed streamed run off "
                         "the host-bound floor; bench records the gated "
                         "key only on hosts with >= 2 usable cores, "
                         "where the two processes' compute genuinely "
                         "overlaps)")
    ap.add_argument("--churn-overhead-threshold", type=float, default=0.10,
                    help="max tolerated dynamic-vs-static round-time "
                         "overhead ratio in the NEW record's churn leg "
                         "(default 0.10 — the 10x population-growth "
                         "registration stream must ride the round at "
                         "marginal cost)")
    ap.add_argument("--span-overhead-threshold", type=float, default=0.05,
                    help="max tolerated span_trace=on round-time overhead "
                         "ratio in the NEW record's spans leg (default "
                         "0.05 — the distributed tracer's cheap-enough-"
                         "to-leave-on promise)")
    ap.add_argument("--model-drift-threshold", type=float, default=0.35,
                    help="max tolerated |model_error_ratio - 1| in the NEW "
                         "record's costmodel leg, per program (default "
                         "0.35: the band covers the documented ~25% "
                         "device-vs-wall host-side share on the cnn "
                         "headline plus fit residuals)")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable comparison as JSON")
    args = ap.parse_args(argv)

    with open(args.old) as f:
        old = json.load(f)
    with open(args.new) as f:
        new = json.load(f)

    reason = check_comparable(old, new)
    if reason and not args.force:
        print(f"REFUSED: {reason}", file=sys.stderr)
        return 2
    if old.get("config_hash") is None or new.get("config_hash") is None:
        print(
            "note: at least one record predates the provenance stamp "
            "(bench schema v1); comparability is not verifiable",
            file=sys.stderr,
        )

    result = compare_records(old, new, threshold=args.threshold)
    for gate in (
        overhead_gate(new, args.stats_overhead_threshold),
        async_speedup_gate(new, args.async_speedup_threshold),
        stream_overlap_gate(new, args.stream_overlap_threshold),
        stream_cohort_rate_gate(new, args.stream_cohort_rate_threshold),
        sweep_amortization_gate(new, args.sweep_amortization_threshold),
        valuation_corr_gate(new, args.valuation_corr_threshold),
        gtg_scaling_gate(new, args.gtg_scaling_threshold),
        churn_overhead_gate(new, args.churn_overhead_threshold),
        mhost_cohort_rate_gate(new, args.mhost_cohort_rate_threshold),
        span_overhead_gate(new, args.span_overhead_threshold),
    ):
        if gate is not None:
            result["regressions"].append(gate)
    result["regressions"].extend(
        model_drift_gate(new, args.model_drift_threshold)
    )
    if args.json:
        print(json.dumps(result, indent=2))
    else:
        for title, key in (("REGRESSIONS", "regressions"),
                           ("improvements", "improvements"),
                           ("within threshold", "unchanged")):
            if result[key]:
                print(f"{title}:")
                for entry in result[key]:
                    print(_fmt(entry))
        if result["skipped"]:
            print("skipped (absent on one side): "
                  + ", ".join(e["metric"] for e in result["skipped"]))
    return 1 if result["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
