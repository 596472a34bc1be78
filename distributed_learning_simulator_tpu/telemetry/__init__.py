"""Run telemetry: per-phase timing, recompile tracking, HBM watermarks.

The reference simulator has zero performance instrumentation (SURVEY §5),
and before this subsystem the reproduction measured cost as one opaque
``round_seconds`` wall-clock number. This package is the observability
layer every execution path (vmap simulator, threaded oracle, multihost
engine) reports through:

* :mod:`.clock` + :mod:`.spans` — the ONE monotonic/wall clock
  convention every timing subsystem shares, and the ONE span recorder
  of a run: alive from the first line of ``run_simulation`` to its
  return whenever ``telemetry_level != 'off'``, one call per boundary
  (profiler annotation + span with parent and round + phase
  accumulation), set-up kept whole, counters for rounds, host syncs and
  jax's trace/lower/compile durations, readable afterwards through
  ``spans.last_run()``. ``span_trace='on'`` adds the per-host
  ``spans_<host_id>.jsonl`` journals (DCN barrier waits, prefetch
  occupancy, checkpoint barriers), the crash flight recorder and
  ``scripts/trace_timeline.py``'s cross-host timeline
  (docs/OBSERVABILITY.md § Spans).
* :mod:`.phases` — the per-round phase accumulation ``phase_seconds`` is
  built from (client step / aggregate / eval / host-sync / post-round),
  fed by the recorder's spans; ``block_until_ready`` fencing only when
  ``telemetry_level='detailed'`` asks for it, so the default program is
  untouched. The threaded oracle times its own phases with it.
* :mod:`.recompile` — an XLA recompilation counter hooked on
  ``jax.monitoring`` compile events, which name their program and, with
  the persistent cache's hit / miss events, say whether it was compiled
  or loaded: any compile after the warmup round flags a
  shape-instability bug with the offending function name.
* :mod:`.memory` — the ONE ``memory_stats()`` probe (HBM watermark +
  capacity), replacing the ad-hoc call sites that used to be duplicated
  in simulator.py and scripts/measure_gtg_scale.py.
* :mod:`.client_stats` — trace-time-gated per-client training
  statistics computed INSIDE the compiled round (streaming reductions;
  no materialized per-client stack), a host-side median/MAD anomaly
  detector attributing which clients drove or corrupted a round, and
  the ``client_stats`` sub-object of the schema-v3 metrics record.
* :mod:`.costmodel` + :mod:`.topologies` — the predictive roofline
  cost model: the categorized traced-op ledger
  (utils/tracing.categorize_ops) evaluated against a checked-in
  topology table to predict per-round device time, per-category
  bottleneck attribution, and $/converged-run on pods the program has
  never touched — the ``costmodel`` sub-object of the schema-v6
  metrics record, the bench ``costmodel`` leg, and compare_bench's
  model-vs-measured drift gate (docs/OBSERVABILITY.md § Cost model).

Records land in ``metrics.jsonl`` through the schema-versioned builder in
``utils/reporting.py``; ``scripts/report_run.py`` renders an artifacts
dir offline. Levels, schema, and interpretation: docs/OBSERVABILITY.md.
"""

from distributed_learning_simulator_tpu.config import (
    CLIENT_STATS_LEVELS,
    TELEMETRY_LEVELS,
)
from distributed_learning_simulator_tpu.telemetry.client_stats import (
    PER_CLIENT_CAP,
    STAT_FIELDS,
    ClientStats,
    attribution_crosscheck,
    client_stats_record,
    detect_and_record,
    detect_anomalies,
)
from distributed_learning_simulator_tpu.telemetry.costmodel import (
    CONVERGED_RUN_ROUNDS,
    DEFAULT_ANCHOR,
    DEFAULT_EFFICIENCY,
    costmodel_record,
    ledger_totals,
    predict_round,
)
from distributed_learning_simulator_tpu.telemetry.memory import (
    device_memory_limit,
    device_memory_stats,
    hbm_limit_bytes,
    peak_hbm_bytes,
)
from distributed_learning_simulator_tpu.telemetry.phases import (
    NullPhaseTimer,
    PhaseTimer,
    make_phase_timer,
)
from distributed_learning_simulator_tpu.telemetry.recompile import (
    RecompileMonitor,
    log_round_compiles,
)
from distributed_learning_simulator_tpu.telemetry.spans import (
    NullTracer,
    SpanRecorder,
    journal_filename,
    last_run,
    start_run,
)
from distributed_learning_simulator_tpu.telemetry.topologies import (
    TOPOLOGIES,
    Topology,
    get_topology,
)
from distributed_learning_simulator_tpu.telemetry.valuation import (
    ClientValuation,
    ValuationAuditor,
    ValuationState,
    cohort_crc,
    pearson_corr,
    spearman_corr,
    valuation_record,
)

__all__ = [
    "CLIENT_STATS_LEVELS",
    "CONVERGED_RUN_ROUNDS",
    "DEFAULT_ANCHOR",
    "DEFAULT_EFFICIENCY",
    "PER_CLIENT_CAP",
    "STAT_FIELDS",
    "TELEMETRY_LEVELS",
    "TOPOLOGIES",
    "ClientStats",
    "ClientValuation",
    "NullPhaseTimer",
    "NullTracer",
    "PhaseTimer",
    "RecompileMonitor",
    "SpanRecorder",
    "Topology",
    "ValuationAuditor",
    "ValuationState",
    "attribution_crosscheck",
    "client_stats_record",
    "cohort_crc",
    "costmodel_record",
    "detect_and_record",
    "detect_anomalies",
    "device_memory_limit",
    "device_memory_stats",
    "get_topology",
    "hbm_limit_bytes",
    "journal_filename",
    "last_run",
    "ledger_totals",
    "log_round_compiles",
    "make_phase_timer",
    "peak_hbm_bytes",
    "pearson_corr",
    "predict_round",
    "spearman_corr",
    "start_run",
    "valuation_record",
]
