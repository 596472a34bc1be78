"""Per-client training statistics, computed INSIDE the jitted round.

PR 3 made the *runtime* observable (phase timings, recompiles, HBM
watermarks) but the training dynamics stayed a black box: a round
reported one accuracy number and nothing about which of the N clients
drove it, diverged, or was corrupted — even though the failure injector
(robustness/faults.py) can corrupt clients that no subsystem could
detect or attribute. Reference simulators treat per-client metrics as a
first-class output (FedJAX's per-client evaluation stream, FL_PyTorch's
per-client optimization statistics); at hardware speed they must be
computed inside the compiled round — no host syncs, no materialized
per-client parameter stacks.

Design, mirroring :mod:`robustness.faults`:

* :class:`ClientStats` is built from config (``client_stats='off'``
  returns None, and every call site gates at TRACE time on that — the
  default compiles the exact pre-feature program, same RNG streams,
  same HLO).
* Per client the round program computes a compact f32 stats vector
  (:data:`STAT_FIELDS`): local loss before/after the local run, the L2
  norm of the uploaded update, the mean per-step gradient norm, the
  cosine of the client's update against the aggregate update, and the
  count of non-finite uploaded elements. All of it comes from STREAMING
  per-chunk reductions — O(1) scalars plus a strided
  ``client_stats_probe``-coordinate delta probe per client — so the
  fused and bucketed aggregation paths never materialize the
  ``[n_clients, n_params]`` stack. Stats are stacked ``[N, S]`` on
  device and fetched once per ``client_stats_every`` rounds inside the
  round's single metric ``device_get``, preserving async dispatch.
* The cosine uses the probe coordinates (exact when the model has at
  most ``client_stats_probe`` parameters); norms and counts are exact
  full reductions.
* Host-side, :func:`detect_anomalies` is a median/MAD outlier detector:
  robust z-scores flag anomalous clients per round with a reason
  (``non_finite`` catches ``corrupt_nan`` uploads; a high-side
  ``update_norm`` z-score catches ``corrupt_scale``; a high-side
  ``loss_after`` z-score catches genuinely diverging clients). High-side
  only: a zero-size update (an empty Dirichlet shard) is not an anomaly.
  The MAD rules assume an honest majority — with more than half the
  cohort corrupt the median itself is poisoned, the same assumption
  every robust aggregation rule makes.
* :func:`client_stats_record` builds the ``client_stats`` sub-object of
  the schema-v3 metrics record (quantile summaries always; raw
  per-client values only for cohorts of at most :data:`PER_CLIENT_CAP`
  clients, so large-N runs don't bloat metrics.jsonl), shared by the
  vmap simulator and the threaded oracle.

Levels, layout, cadence, and detector tuning: docs/OBSERVABILITY.md;
the detection side of fault injection: docs/ROBUSTNESS.md.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from distributed_learning_simulator_tpu.config import CLIENT_STATS_LEVELS

#: Column order of the per-client ``[N, S]`` stats matrix. Fields an
#: execution path cannot produce (the threaded oracle's workers report
#: no losses) are NaN and render as null in the record.
STAT_FIELDS = (
    "loss_before",      # first-step local loss (global params, 1st batch)
    "loss_after",       # final-epoch mean local loss
    "update_norm",      # L2 norm of the uploaded delta (post-corruption)
    "grad_norm",        # sqrt(mean per-step squared gradient L2 norm)
    "agg_cosine",       # cos(client delta, aggregate delta) over the probe
    "nonfinite_count",  # non-finite elements in the upload (exact count)
)

_IDX = {name: i for i, name in enumerate(STAT_FIELDS)}

#: Cohorts up to this size get raw per-client values in the record
#: (report_run's per-client loss sparklines); larger cohorts get
#: quantile summaries only.
PER_CLIENT_CAP = 32

#: Quantiles summarizing each stat column in the record.
_QUANTILES = (0, 25, 50, 75, 100)


@dataclass(frozen=True)
class ClientStats:
    """Static (trace-time) client-statistics configuration; the per-round
    reductions are pure functions of round state, so one compiled round
    program serves every round."""

    every: int = 1
    probe: int = 4096
    mad_threshold: float = 8.0

    @classmethod
    def from_config(cls, config) -> "ClientStats | None":
        """None when ``client_stats='off'`` — callers gate every
        trace-time branch on that, so off-mode runs compile the exact
        pre-feature program."""
        level = (getattr(config, "client_stats", "off") or "off").lower()
        if level == "off":
            return None
        if level not in CLIENT_STATS_LEVELS:
            raise ValueError(
                f"unknown client_stats {level!r}; known: "
                + ", ".join(CLIENT_STATS_LEVELS)
            )
        return cls(
            every=int(getattr(config, "client_stats_every", 1)),
            probe=int(getattr(config, "client_stats_probe", 4096)),
            mad_threshold=float(
                getattr(config, "client_stats_mad_threshold", 8.0)
            ),
        )

    def fetch_round(self, round_idx: int) -> bool:
        """Whether this round's stats are fetched to host (the device
        computes them every round; only the device->host transfer is on
        the ``client_stats_every`` cadence)."""
        return round_idx % self.every == 0

    # ---- jit-side streaming reductions ------------------------------------
    def _stride(self, tree) -> int:
        total = sum(
            leaf.size for leaf in jax.tree_util.tree_leaves(tree)
        )
        return max(1, total // max(self.probe, 1))

    def probe_delta(self, base_tree, new_tree):
        """``[K]`` strided probe of ``new - base`` (one model). The SAME
        stride/leaf-order as :meth:`add_upload_stats` samples, so client
        probes and the aggregate probe cover identical coordinates."""
        stride = self._stride(base_tree)
        rows = [
            (n.astype(jnp.float32) - b.astype(jnp.float32)).reshape(-1)[
                ::stride
            ]
            for b, n in zip(
                jax.tree_util.tree_leaves(base_tree),
                jax.tree_util.tree_leaves(new_tree),
            )
        ]
        return jnp.concatenate(rows)

    def add_upload_stats(self, train_metrics: dict, global_params,
                         stacked) -> dict:
        """Fold per-client upload statistics into the train-metrics dict
        (leading axis of every ``stacked`` leaf = clients). Called once
        per chunk on the fused/bucketed paths — the per-client outputs
        are O(1) scalars plus the ``[chunk, K]`` probe, never the stack
        — and once on the full stack on the materializing path. Applied
        AFTER fault corruption: the stats describe what the server
        received."""
        stride = self._stride(global_params)
        sq = 0.0
        nonfinite = 0.0
        probes = []
        for g, c in zip(
            jax.tree_util.tree_leaves(global_params),
            jax.tree_util.tree_leaves(stacked),
        ):
            d = c.astype(jnp.float32) - g.astype(jnp.float32)
            flat = d.reshape((d.shape[0], -1))
            sq = sq + jnp.sum(flat * flat, axis=1)
            nonfinite = nonfinite + jnp.sum(
                (~jnp.isfinite(c.reshape((c.shape[0], -1)))).astype(
                    jnp.float32
                ),
                axis=1,
            )
            probes.append(flat[:, ::stride])
        out = dict(train_metrics)
        out["update_sq"] = sq
        out["nonfinite_count"] = nonfinite
        out["stat_probe"] = jnp.concatenate(probes, axis=1)
        return out

    def stats_matrix(self, train_metrics: dict, agg_probe) -> jnp.ndarray:
        """Assemble the ``[N, S]`` stats matrix (:data:`STAT_FIELDS`
        column order) from the collected per-client metrics and the
        aggregate-delta probe. Missing loss/grad columns (an execution
        path that cannot produce them) fill with NaN."""
        probe = train_metrics["stat_probe"]
        n = probe.shape[0]
        nan = jnp.full((n,), jnp.nan, jnp.float32)
        dots = probe @ agg_probe
        denom = (
            jnp.linalg.norm(probe, axis=1) * jnp.linalg.norm(agg_probe)
            + 1e-12
        )
        grad_sq = train_metrics.get("grad_sq_mean")
        cols = (
            train_metrics.get("loss_first", nan),
            train_metrics.get("loss", nan),
            jnp.sqrt(train_metrics["update_sq"]),
            nan if grad_sq is None else jnp.sqrt(grad_sq),
            dots / denom,
            train_metrics["nonfinite_count"],
        )
        return jnp.stack(
            [c.astype(jnp.float32) for c in cols], axis=1
        )

    def stack_stats(self, prev_global, stacked, aggregated) -> jnp.ndarray:
        """One-shot ``[N, S]`` stats from a materialized upload stack and
        the raw aggregate (the threaded oracle's path: it holds the stack
        at the rendezvous barrier but its workers report no losses)."""
        tm = self.add_upload_stats({}, prev_global, stacked)
        return self.stats_matrix(tm, self.probe_delta(prev_global, aggregated))


# ---- host-side detection + record building --------------------------------


def detect_anomalies(stats: np.ndarray, mad_threshold: float = 8.0):
    """Median/MAD outlier detection over one round's ``[N, S]`` stats.

    Returns ``(flagged, reasons)``: a sorted list of flagged row indices
    and ``{row: reason}`` ("+"-joined when several rules fire). Rules:

    * ``non_finite`` — any non-finite uploaded element (catches
      ``corrupt_nan`` regardless of how many clients are corrupt);
    * ``update_norm`` / ``loss_diverged`` — robust z-score
      ``(x - median) / (1.4826 * MAD)`` above ``mad_threshold``,
      HIGH side only (a small update is an empty shard, not an attack).
      Computed over ACTIVE clients only — rows with ``update_norm == 0``
      never trained (empty Dirichlet shards, whose all-zero stats rows
      the bucketed path emits by design) and are excluded from both the
      median/MAD population and the flaggable set, so a mostly-empty
      cohort cannot collapse the median to 0 and mark every honest
      client an outlier. Needs at least 3 active finite values; with
      MAD 0 (identical updates) the denominator floors at
      ``1e-6 * |median|`` so float jitter never flags, while a
      100x-scaled upload still scores astronomically.

    The z rules assume an honest majority — the same assumption the
    robust aggregation rules make. Pure numpy (no jax import cost in the
    hot loop; unit-testable without a backend).
    """
    stats = np.asarray(stats, dtype=np.float64)
    n = stats.shape[0]
    reasons: dict[int, list[str]] = {}

    def flag(i: int, reason: str) -> None:
        reasons.setdefault(int(i), []).append(reason)

    nonfinite = np.nan_to_num(stats[:, _IDX["nonfinite_count"]], nan=1.0)
    for i in np.flatnonzero(nonfinite > 0):
        flag(i, "non_finite")
    # Active = actually uploaded something: zero-norm rows are empty
    # shards (the bucketed path's skipped clients keep all-zero rows),
    # excluded from the z population AND from flagging so they can
    # neither be outliers nor drag the median to 0.
    upd = stats[:, _IDX["update_norm"]]
    active = np.isfinite(upd) & (upd > 0.0)
    if n >= 3:
        for col, reason in (
            ("update_norm", "update_norm"),
            ("loss_after", "loss_diverged"),
        ):
            x = stats[:, _IDX[col]]
            ok = active & np.isfinite(x)
            if ok.sum() < 3:
                continue
            med = float(np.median(x[ok]))
            mad = float(np.median(np.abs(x[ok] - med)))
            denom = max(1.4826 * mad, 1e-6 * abs(med), 1e-12)
            z = (x - med) / denom
            for i in np.flatnonzero(ok & (z > mad_threshold)):
                flag(i, reason)
    flagged = sorted(reasons)
    return flagged, {i: "+".join(r) for i, r in reasons.items()}


def _san(v) -> float | None:
    """JSON-safe scalar: non-finite floats become None (metrics.jsonl
    must stay strict JSON — NaN is not)."""
    v = float(v)
    return v if np.isfinite(v) else None


def client_stats_record(stats: np.ndarray, flagged, reasons,
                        participants=None, extras: dict | None = None,
                        per_client_cap: int = PER_CLIENT_CAP) -> dict:
    """Build the ``client_stats`` sub-object of a schema-v3 metrics
    record — the ONE shape both execution paths emit
    (utils/reporting.build_round_record attaches it).

    ``participants`` (optional ``[N]`` int array) maps stats rows to true
    client ids under participation sampling. ``extras`` merges
    algorithm-specific round scalars (fed_quant's ``quant_mse``,
    sign_SGD's ``vote_agreement``).
    """
    stats = np.asarray(stats, dtype=np.float64)
    n = stats.shape[0]
    ids = (
        np.arange(n, dtype=np.int64) if participants is None
        else np.asarray(participants, dtype=np.int64)
    )
    quantiles = {}
    for name, col in _IDX.items():
        x = stats[:, col]
        finite = x[np.isfinite(x)]
        quantiles[name] = {
            f"p{q}": (
                round(float(np.percentile(finite, q)), 6)
                if finite.size else None
            )
            for q in _QUANTILES
        }
    record: dict = {
        "n_clients": n,
        "flagged_clients": [int(ids[i]) for i in flagged],
        "flag_reason": {str(int(ids[i])): reasons[i] for i in flagged},
        "quantiles": quantiles,
    }
    if n <= per_client_cap:
        per_client: dict = {"client_ids": [int(i) for i in ids]}
        for name, col in _IDX.items():
            per_client[name] = [
                round(float(x), 6) if np.isfinite(x) else None
                for x in stats[:, col]
            ]
        record["per_client"] = per_client
    if extras:
        record.update({k: _san(v) for k, v in extras.items()})
    return record


def detect_and_record(stats, cs: "ClientStats", round_idx: int,
                      logger=None, participants=None,
                      extras: dict | None = None):
    """One round's host-side flagging pipeline — detector, record
    builder, WARNING log — shared verbatim by the vmap simulator and the
    threaded oracle so the two paths cannot drift. Returns
    ``(record, n_flagged)``."""
    stats = np.asarray(stats)
    flagged, reasons = detect_anomalies(stats, cs.mad_threshold)
    record = client_stats_record(
        stats, flagged, reasons, participants=participants, extras=extras
    )
    if flagged and logger is not None:
        logger.warning(
            "round %d: client-stats detector flagged clients %s (%s)",
            round_idx, record["flagged_clients"], record["flag_reason"],
        )
    return record, len(flagged)


def attribution_crosscheck(shapley_values: np.ndarray,
                           stats: np.ndarray) -> float | None:
    """Cross-check Shapley utility attribution against the in-round
    statistics: Pearson correlation between per-client Shapley value and
    local loss improvement (``loss_before - loss_after``). A strongly
    negative value says the expensive attribution and the cheap
    per-client signal disagree — worth a look either way. None when
    either side is degenerate (too few finite pairs, zero variance)."""
    sv = np.asarray(shapley_values, dtype=np.float64)
    stats = np.asarray(stats, dtype=np.float64)
    improve = stats[:, _IDX["loss_before"]] - stats[:, _IDX["loss_after"]]
    ok = np.isfinite(sv) & np.isfinite(improve)
    if ok.sum() < 2:
        return None
    sv, improve = sv[ok], improve[ok]
    if np.ptp(sv) == 0.0 or np.ptp(improve) == 0.0:
        return None
    return float(np.corrcoef(sv, improve)[0, 1])


def expert_load_record(model_counts: dict) -> dict:
    """The round's routing counters (the round program's ``model_counts``
    aux, summed over the cohort's local steps; models/solar_open2.py) as a
    record: per layer the assignments that fell to each expert held here,
    their share of all the round's assignments' tokens, the fullest held
    expert over the mean (1.0 = even), and the steps in which a held
    expert outgrew its slots and the layer fell back to every token."""
    load = np.asarray(model_counts["moe_expert_load"], dtype=np.int64)
    load = load.reshape((-1,) + load.shape[-2:]).sum(axis=0)
    tokens = np.asarray(model_counts["moe_routed_tokens"]).reshape(
        -1, load.shape[0]).sum(axis=0)
    mean = np.maximum(load.mean(axis=1), 1e-9)
    return {
        "load": load.tolist(),
        "assignments_per_token": [
            round(float(a), 6) for a in load.sum(axis=1) / np.maximum(tokens, 1)
        ],
        "max_over_mean": [round(float(r), 4) for r in load.max(axis=1) / mean],
        "overflow_steps": np.asarray(
            model_counts["moe_overflows"]
        ).reshape(-1, load.shape[0]).sum(axis=0).tolist(),
    }
