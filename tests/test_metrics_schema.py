"""metrics.jsonl record layouts vs the checked-in JSON schema.

Jax-free (imports only utils.reporting + jsonschema): the schema at
tests/data/metrics_record.schema.json is the reviewable contract every
emitter (vmap simulator, threaded oracle, sweep engine) writes through
``build_round_record``. v1 (legacy), v2 (+telemetry), v3
(+client_stats), v4 (+async), v5 (+stream), v6 (+costmodel), v7
(+valuation), v8 (+sweep), v9 (+population), v10 (+gtg), v11
(+multihost) and v12 (+spans) records must validate;
records that mix versions and sub-objects inconsistently must not. The
integration tests in test_client_stats.py (test_costmodel.py for v6,
test_valuation.py for v7, test_sweep.py for v8, test_population.py for
v9, test_gtg_mesh.py for v10, test_multihost.py's 2-process harness
for v11 and v12 with span_trace='on') validate REAL produced records
against the same file.
"""

import json
import os

import jsonschema
import pytest

from distributed_learning_simulator_tpu.utils.reporting import (
    METRICS_SCHEMA_VERSION,
    _GTG_SCHEMA_VERSION,
    _MULTIHOST_SCHEMA_VERSION,
    build_round_record,
)

_SCHEMA_PATH = os.path.join(
    os.path.dirname(__file__), "data", "metrics_record.schema.json"
)


def load_schema() -> dict:
    with open(_SCHEMA_PATH) as f:
        return json.load(f)


def validate(record: dict) -> None:
    jsonschema.validate(record, load_schema())


def _base() -> dict:
    return {
        "round": 3,
        "test_accuracy": 0.61,
        "test_loss": 1.1,
        "mean_client_loss": 1.2,
        "round_seconds": 0.41,
    }


def _telemetry() -> dict:
    return {
        "phase_seconds": {"client_step": 0.31, "eval": 0.04,
                          "host_sync": 0.05, "post_round": 0.0},
        "compiles": 1,
        "compiled": ["round_fn"],
        "peak_hbm_bytes": 9126805504,
    }


def _client_stats() -> dict:
    return {
        "n_clients": 4,
        "flagged_clients": [2],
        "flag_reason": {"2": "non_finite+update_norm"},
        "quantiles": {
            "loss_before": {"p0": 2.1, "p25": 2.2, "p50": 2.3, "p75": 2.4,
                            "p100": 2.5},
            "update_norm": {"p0": 0.1, "p25": 0.2, "p50": 0.2, "p75": 0.3,
                            "p100": None},
        },
        "per_client": {
            "client_ids": [0, 1, 2, 3],
            "loss_after": [2.0, 2.1, None, 2.2],
            "update_norm": [0.1, 0.2, None, 0.3],
        },
        "quant_mse": 1e-06,
    }


def test_schema_file_is_valid_draft7():
    jsonschema.Draft7Validator.check_schema(load_schema())


def test_v1_record_validates():
    record = build_round_record(_base(), None, None)
    assert record is not None and "schema_version" not in record
    validate(record)
    # Algorithm extras (compression ratios, shapley dicts rendered as
    # numbers by the host loop's filter) are allowed in every version.
    validate({**_base(), "uplink_compression_ratio": 4.0,
              "survivor_count": 7, "round_rejected": False})


def test_v2_record_validates():
    record = build_round_record(_base(), _telemetry())
    assert record["schema_version"] == 2
    validate(record)


def test_v2_batched_dispatch_record_validates():
    """Records of older trees, which could fuse several rounds into one
    dispatch, carry dispatch_rounds + a warmup marker in their telemetry.
    Nothing emits either now; the schema still admits them as plain v2."""
    tel = {**_telemetry(), "dispatch_rounds": 8, "warmup": True}
    record = build_round_record(_base(), tel)
    assert record["schema_version"] == 2
    validate(record)


def _async() -> dict:
    return {
        "on_time": 6, "late": 2, "buffer": 5, "applied": False,
        "mean_staleness": 1.5,
        "sim_round_s": 1.5, "sim_round_sync_s": 11.2, "sim_clock_s": 19.5,
    }


def test_v3_record_validates():
    record = build_round_record(_base(), _telemetry(), _client_stats())
    assert record["schema_version"] == 3
    validate(record)
    # client_stats without telemetry (telemetry_level='off') is still v3.
    validate(build_round_record(_base(), None, _client_stats()))
    # Round-scalar-only sub-object (sign_SGD's vote agreement).
    validate(build_round_record(
        _base(), None, {"n_clients": 4, "vote_agreement": 0.93}
    ))


def test_v4_record_validates():
    record = build_round_record(
        _base(), _telemetry(), _client_stats(), _async()
    )
    assert record["schema_version"] == 4
    validate(record)
    # async alone (telemetry_level='off', client_stats='off') is still v4.
    validate(build_round_record(_base(), None, None, _async()))
    # A quiet round: nothing late -> null mean staleness.
    validate(build_round_record(_base(), None, None, {
        **_async(), "late": 0, "mean_staleness": None,
    }))


def _stream() -> dict:
    return {
        "h2d_bytes": 655360, "h2d_seconds": 0.0123,
        "hidden_seconds": 0.0119, "overlap_ratio": 0.9675,
        "d2h_bytes": 1024, "d2h_seconds": 0.0004,
    }


def test_v5_record_validates():
    record = build_round_record(
        _base(), _telemetry(), _client_stats(), _async(), _stream()
    )
    assert record["schema_version"] == 5
    validate(record)
    # stream alone (every other feature off) is still v5.
    validate(build_round_record(_base(), None, None, None, _stream()))
    # Stateless runs carry no d2h fields; older trees' records may stamp
    # the rounds a transfer covered (dispatch_rounds).
    validate(build_round_record(_base(), None, None, None, {
        "h2d_bytes": 655360, "h2d_seconds": 0.0123,
        "hidden_seconds": 0.0, "overlap_ratio": 0.0,
        "dispatch_rounds": 4,
    }))
    # Sampled-cohort uploads name the sampler + the cohort-draw replay
    # cost (participation_sampler, ops/sampling.py) — still v5.
    for sampler in ("exact", "hashed"):
        validate(build_round_record(_base(), None, None, None, {
            **_stream(), "sampler": sampler, "sample_ms": 1203.4,
        }))
    # An unknown sampler name is a schema break, not a silent extension.
    with pytest.raises(jsonschema.ValidationError):
        validate(build_round_record(_base(), None, None, None, {
            **_stream(), "sampler": "quantum", "sample_ms": 0.1,
        }))


def _costmodel() -> dict:
    return {
        "anchor_topology": "v5e-1",
        "predicted_ms": 2274.2,
        "measured_ms": 2275.4,
        "model_error_ratio": 0.9995,
        "bottleneck": "memory",
        "trace_rounds": 1,
        "run_rounds": 150,
        "categories": {
            "matmul_conv": {
                "bytes_gb": 348.967, "device_ms": 675.3, "flops_g": 0.0,
                "predicted_ms": 635.5, "bottleneck": "memory",
            },
            "elementwise": {
                "bytes_gb": 900.0, "device_ms": 1600.0, "flops_g": 0.0,
                "predicted_ms": 1638.7, "bottleneck": "memory",
            },
        },
        "per_topology": {
            "v5e-1": {"chips": 1, "predicted_ms": 2274.2,
                      "bottleneck": "memory", "usd_per_round": 0.000758,
                      "usd_per_run": 0.1137},
            "v4-32": {"chips": 32, "predicted_ms": 47.4,
                      "bottleneck": "memory", "usd_per_round": 0.001357,
                      "usd_per_run": 0.2035},
        },
    }


def test_v6_record_validates():
    record = build_round_record(
        _base(), _telemetry(), _client_stats(), _async(), _stream(),
        _costmodel(),
    )
    assert record["schema_version"] == 6
    validate(record)
    # costmodel alone (every other feature off) is still v6 — the
    # simulator's last-round record under cost_model_trace with
    # telemetry_level='off'.
    validate(build_round_record(
        _base(), None, None, None, None, _costmodel()
    ))
    # Prediction without a measured anchor (offline pricing of a trace).
    validate(build_round_record(_base(), None, None, None, None, {
        **_costmodel(), "measured_ms": None, "model_error_ratio": None,
    }))


def _valuation() -> dict:
    return {
        "n_clients": 4,
        "updated": 3,
        "loss_delta": 0.0412,
        "top_clients": [{"id": 0, "value": 0.0051}, {"id": 3, "value": 0.0047}],
        "bottom_clients": [{"id": 2, "value": 0.0012}, {"id": 1, "value": 0.003}],
        "per_client": {
            "client_ids": [0, 1, 2, 3],
            "value": [0.0051, 0.003, 0.0012, 0.0047],
        },
        "audit": {
            "spearman": 0.881, "pearson": 0.506, "spearman_round": 0.881,
            "audits": 2, "permutations": 225, "subset_evals": 466,
            "converged": True, "memo_hit_rate": None, "seconds": 2.48,
        },
    }


def test_v7_record_validates():
    record = build_round_record(
        _base(), _telemetry(), _client_stats(), _async(), _stream(),
        _costmodel(), _valuation(),
    )
    assert record["schema_version"] == 7
    validate(record)
    # valuation alone (every other feature off) is still v7 — a
    # client_valuation='on' run with telemetry_level='off' ... except
    # valuation requires client_stats='on', so the realistic minimum
    # carries both; the schema allows either.
    validate(build_round_record(
        _base(), None, None, None, None, None, _valuation()
    ))
    validate(build_round_record(
        _base(), None, _client_stats(), None, None, None, _valuation()
    ))
    # Non-audit rounds carry no audit sub-object; degenerate
    # correlations (all-zero vector on round 1) are null.
    no_audit = {k: v for k, v in _valuation().items() if k != "audit"}
    validate(build_round_record(
        _base(), None, None, None, None, None, no_audit
    ))
    validate(build_round_record(
        _base(), None, None, None, None, None,
        {**_valuation(), "audit": {
            "spearman": None, "pearson": None, "spearman_round": None,
            "audits": 1, "permutations": 8, "subset_evals": 12,
            "converged": False, "memo_hit_rate": 0.5, "seconds": 0.1,
        }},
    ))


def _sweep() -> dict:
    return {
        "point": 3,
        "seed": 7,
        "lr": 0.1,
        "strategy": "vmapped",
        "group": "9c2f3e1a4b5d",
        "compile_reused": True,
        "experiments": 8,
    }


def test_v8_record_validates():
    record = build_round_record(
        _base(), _telemetry(), _client_stats(), _async(), _stream(),
        _costmodel(), _valuation(), _sweep(),
    )
    assert record["schema_version"] == 8
    validate(record)
    # sweep alone (every other feature off) is still v8 — the sweep
    # engine's per-point records at defaults.
    validate(build_round_record(_base(), sweep=_sweep()))
    # Scheduled points carry no fleet width (experiments is vmapped-only)
    # and may carry the usual round extras (cohort_hash, lr_factor).
    sched = {k: v for k, v in _sweep().items() if k != "experiments"}
    sched["strategy"] = "scheduled"
    sched["compile_reused"] = False
    validate(build_round_record(
        {**_base(), "cohort_hash": 12345, "lr_factor": 0.5,
         "mean_client_loss": 1.2},
        sweep=sched,
    ))


def _population() -> dict:
    return {
        "n_initial": 8,
        "n_registered": 16,
        "n_alive": 14,
        "joins": 2,
        "departs": 1,
        "cohort_departs": 1,
        "drift_cohort_size": 3,
        "rejected_by_churn": False,
        "drift_clients": [1, 4, 6],
    }


def test_v9_record_validates():
    record = build_round_record(
        _base(), _telemetry(), _client_stats(), _async(), _stream(),
        _costmodel(), _valuation(), _sweep(), _population(),
    )
    assert record["schema_version"] == 9
    validate(record)
    # population alone (every other feature off) is still v9 — a
    # dynamic-population run at default telemetry.
    validate(build_round_record(_base(), population=_population()))
    # A churn-rejected round carries the quorum fields too; large drift
    # cohorts report the size only (no id list).
    big = {k: v for k, v in _population().items()
           if k != "drift_clients"}
    big["drift_cohort_size"] = 500
    big["rejected_by_churn"] = True
    validate(build_round_record(
        {**_base(), "cohort_hash": 99, "survivor_count": 2,
         "round_rejected": True, "mean_client_loss": 1.2},
        population=big,
    ))


def _gtg() -> dict:
    return {
        "devices": 2,
        "evals_per_s": 1412.5,
        "wave_width": 32,
        "walk_seconds": 4.731,
    }


def test_v10_record_validates():
    record = build_round_record(
        _base(), _telemetry(), _client_stats(), _async(), _stream(),
        _costmodel(), _valuation(), _sweep(), _population(), _gtg(),
    )
    assert record["schema_version"] == _GTG_SCHEMA_VERSION == 10
    validate(record)
    # gtg alone (every other feature off) is still v10 — a mesh-sharded
    # GTG run at default telemetry. (keep_client_params always leaves
    # the shapley extras as base-record scalars, allowed in every
    # version like the other algorithm extras.)
    validate(build_round_record(
        {**_base(), "gtg_permutations": 40, "gtg_subset_evals": 715,
         "mean_client_loss": 1.2},
        gtg=_gtg(),
    ))
    # A tiny walk can have no throughput sample (0 evals -> null rate).
    validate(build_round_record(
        _base(), gtg={**_gtg(), "evals_per_s": None}
    ))
    # The audit-side face: a v7 valuation audit carrying the walk's
    # device count stays v7 (the gtg sub-object is the GTG server's
    # per-round record, not the auditor's).
    record = build_round_record(
        _base(), None, None, None, None, None,
        {**_valuation(), "audit": {
            **_valuation()["audit"], "devices": 2,
        }},
    )
    assert record["schema_version"] == 7
    validate(record)


def _multihost() -> dict:
    return {
        "hosts": 2,
        "host_id": 0,
        "owned_clients": 500000,
        "shard_bytes": 551182336,
        "spill_rows": 9,
        "dcn_bytes": 41544,
        "h2d_seconds": 0.0041,
        "overlap_ratio": 0.83,
    }


def test_v11_record_validates():
    record = build_round_record(
        _base(), _telemetry(), None, None, _stream(),
        multihost=_multihost(),
    )
    assert record["schema_version"] == _MULTIHOST_SCHEMA_VERSION == 11
    validate(record)
    # multihost alone (default telemetry) is still v11 — a distributed
    # streamed run with everything else off.
    validate(build_round_record(
        {**_base(), "cohort_hash": 7, "mean_client_loss": 1.2},
        multihost=_multihost(),
    ))
    # The full-cohort regime reports structurally-zero spill.
    validate(build_round_record(
        _base(),
        multihost={**_multihost(), "spill_rows": 0, "dcn_bytes": 0},
    ))


def _spans() -> dict:
    return {
        "host_id": 0,
        "hosts": 2,
        "count": 23,
        "dropped": 0,
        "seconds_by_cat": {"phase": 0.412, "dcn_wait": 0.031,
                           "dcn": 0.004, "io": 0.009, "round": 0.46},
        "dcn_wait_s": 0.031,
        "dcn_transfer_s": 0.004,
        "spill_skew_ms": 28.4,
        "ckpt_skew_ms": None,
    }


def test_v12_record_validates():
    record = build_round_record(
        _base(), _telemetry(), None, None, _stream(),
        multihost=_multihost(), spans=_spans(),
    )
    assert record["schema_version"] == METRICS_SCHEMA_VERSION == 12
    validate(record)
    # spans alone (every other feature off) is still v12 — a
    # single-process span_trace='on' run; skews are null on rounds that
    # crossed no barrier, and single-host runs report hosts=1.
    validate(build_round_record(_base(), spans={
        "host_id": 0, "hosts": 1, "count": 5,
        "seconds_by_cat": {"phase": 0.01},
        "dcn_wait_s": 0.0, "dcn_transfer_s": 0.0,
        "spill_skew_ms": None, "ckpt_skew_ms": None,
    }))
    # A buffer-overrun round reports what it dropped.
    validate(build_round_record(
        _base(), spans={**_spans(), "dropped": 12},
    ))


def test_lowest_version_stamping_preserved():
    """Adding v10 must not disturb the lower stamps: the version is the
    LOWEST that describes the record (longitudinal byte-identity)."""
    assert "schema_version" not in build_round_record(_base())
    assert build_round_record(_base(), _telemetry())[
        "schema_version"] == 2
    assert build_round_record(_base(), None, _client_stats())[
        "schema_version"] == 3
    assert build_round_record(_base(), None, None, _async())[
        "schema_version"] == 4
    assert build_round_record(_base(), None, None, None, _stream())[
        "schema_version"] == 5
    assert build_round_record(_base(), None, None, None, None,
                              _costmodel())["schema_version"] == 6
    assert build_round_record(_base(), None, None, None, None, None,
                              _valuation())["schema_version"] == 7
    assert build_round_record(_base(), sweep=_sweep())[
        "schema_version"] == 8
    assert build_round_record(_base(), population=_population())[
        "schema_version"] == 9
    assert build_round_record(_base(), gtg=_gtg())[
        "schema_version"] == 10
    assert build_round_record(_base(), multihost=_multihost())[
        "schema_version"] == 11
    assert build_round_record(_base(), spans=_spans())[
        "schema_version"] == 12


def test_version_content_mismatches_rejected():
    # v2 stamp carrying a client_stats sub-object: the builder never
    # emits it, and the schema must refuse it too.
    bad = build_round_record(_base(), _telemetry())
    bad["client_stats"] = _client_stats()
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    # v3 stamp without the client_stats sub-object.
    bad = build_round_record(_base(), _telemetry())
    bad["schema_version"] = 3
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    # Unversioned record smuggling a telemetry sub-object.
    bad = dict(_base())
    bad["telemetry"] = _telemetry()
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    # Unknown keys inside the versioned sub-objects are schema breaks,
    # not silent extensions.
    bad = build_round_record(_base(), {**_telemetry(), "mystery": 1})
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    bad = build_round_record(
        _base(), None, {**_client_stats(), "mystery": 1}
    )
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    # v3 stamp smuggling an async sub-object (the builder always stamps
    # async records v4).
    bad = build_round_record(_base(), None, _client_stats())
    bad["async"] = _async()
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    bad = build_round_record(
        _base(), None, None, {**_async(), "mystery": 1}
    )
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    # v4 stamp smuggling a stream sub-object (the builder always stamps
    # stream records v5).
    bad = build_round_record(_base(), None, None, _async())
    bad["stream"] = _stream()
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    bad = build_round_record(
        _base(), None, None, None, {**_stream(), "mystery": 1}
    )
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    # v5 stamp smuggling a costmodel sub-object (the builder always
    # stamps costmodel records v6).
    bad = build_round_record(_base(), None, None, None, _stream())
    bad["costmodel"] = _costmodel()
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    # v6 stamp without the costmodel sub-object.
    bad = build_round_record(_base(), _telemetry())
    bad["schema_version"] = 6
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    # Unknown keys inside costmodel (top level, a category, a topology
    # row) are schema breaks, not silent extensions.
    for poison in (
        {"mystery": 1},
        {"categories": {"matmul_conv": {
            "bytes_gb": 1.0, "predicted_ms": 1.0, "bottleneck": "memory",
            "mystery": 1,
        }}},
        {"per_topology": {"v4-32": {"chips": 32, "predicted_ms": 1.0,
                                    "mystery": 1}}},
    ):
        bad = build_round_record(
            _base(), None, None, None, None, {**_costmodel(), **poison}
        )
        with pytest.raises(jsonschema.ValidationError):
            validate(bad)
    # A bottleneck outside the compute/memory/collective enum.
    bad = build_round_record(
        _base(), None, None, None, None,
        {**_costmodel(), "bottleneck": "vibes"},
    )
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    # v6 stamp smuggling a valuation sub-object (the builder always
    # stamps valuation records v7).
    bad = build_round_record(_base(), None, None, None, None, _costmodel())
    bad["valuation"] = _valuation()
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    # v7 stamp without the valuation sub-object.
    bad = build_round_record(_base(), _telemetry())
    bad["schema_version"] = 7
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    # Unknown keys inside valuation (top level, the audit, a ranked
    # entry) are schema breaks, not silent extensions.
    for poison in (
        {"mystery": 1},
        {"audit": {**_valuation()["audit"], "mystery": 1}},
        {"top_clients": [{"id": 0, "value": 1.0, "mystery": 1}]},
        {"per_client": {"client_ids": [0], "value": [1.0], "mystery": 1}},
    ):
        bad = build_round_record(
            _base(), None, None, None, None, None,
            {**_valuation(), **poison},
        )
        with pytest.raises(jsonschema.ValidationError):
            validate(bad)
    # v7 stamp smuggling a sweep sub-object (the builder always stamps
    # sweep records v8).
    bad = build_round_record(_base(), None, None, None, None, None,
                             _valuation())
    bad["sweep"] = _sweep()
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    # v8 stamp without the sweep sub-object.
    bad = build_round_record(_base(), _telemetry())
    bad["schema_version"] = 8
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    # Unknown keys / a strategy outside the vmapped/scheduled enum are
    # schema breaks, not silent extensions.
    for poison in (
        {"mystery": 1},
        {"strategy": "psychic"},
    ):
        bad = build_round_record(_base(), sweep={**_sweep(), **poison})
        with pytest.raises(jsonschema.ValidationError):
            validate(bad)
    # v8 stamp smuggling a population sub-object (the builder always
    # stamps population records v9).
    bad = build_round_record(_base(), sweep=_sweep())
    bad["population"] = _population()
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    # v9 stamp without the population sub-object.
    bad = build_round_record(_base(), _telemetry())
    bad["schema_version"] = 9
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    # Unknown population keys are schema breaks, not silent extensions.
    bad = build_round_record(
        _base(), population={**_population(), "mystery": 1}
    )
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    # v9 stamp smuggling a gtg sub-object (the builder always stamps
    # gtg records v10).
    bad = build_round_record(_base(), population=_population())
    bad["gtg"] = _gtg()
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    # v10 stamp without the gtg sub-object.
    bad = build_round_record(_base(), _telemetry())
    bad["schema_version"] = 10
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    # Unknown gtg keys — and a serial walk claiming the sub-object
    # (devices < 2: serial rounds must keep pre-v10 records) — are
    # schema breaks, not silent extensions.
    for poison in ({"mystery": 1}, {"devices": 1}):
        bad = build_round_record(_base(), gtg={**_gtg(), **poison})
        with pytest.raises(jsonschema.ValidationError):
            validate(bad)
    # v10 stamp smuggling a multihost sub-object (the builder always
    # stamps multihost records v11).
    bad = build_round_record(_base(), gtg=_gtg())
    bad["multihost"] = _multihost()
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    # v11 stamp without the multihost sub-object.
    bad = build_round_record(_base(), _telemetry())
    bad["schema_version"] = 11
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    # Unknown multihost keys — and a single-process run claiming the
    # sub-object (hosts < 2: 1-process streamed runs must keep pre-v11
    # records) — are schema breaks, not silent extensions.
    for poison in ({"mystery": 1}, {"hosts": 1}):
        bad = build_round_record(
            _base(), multihost={**_multihost(), **poison}
        )
        with pytest.raises(jsonschema.ValidationError):
            validate(bad)
    # v11 stamp smuggling a spans sub-object (the builder always stamps
    # span-trace records v12).
    bad = build_round_record(_base(), multihost=_multihost())
    bad["spans"] = _spans()
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    # v12 stamp without the spans sub-object.
    bad = build_round_record(_base(), _telemetry())
    bad["schema_version"] = 12
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)
    # Unknown spans keys are schema breaks, not silent extensions.
    bad = build_round_record(_base(), spans={**_spans(), "mystery": 1})
    with pytest.raises(jsonschema.ValidationError):
        validate(bad)


def test_missing_required_base_fields_rejected():
    record = build_round_record(_base(), _telemetry())
    del record["test_accuracy"]
    with pytest.raises(jsonschema.ValidationError):
        validate(record)
