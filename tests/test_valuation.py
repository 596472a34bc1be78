"""Always-on client valuation (telemetry/valuation.py, ISSUE 9).

Pins the streaming estimator's exact arithmetic (hand-computed 3-client
decay trace), the correlation helpers, the off-gate bit-identity
contract (client_valuation='off' = the exact pre-feature program and
records; config_hash unchanged for pre-feature configs), streamed-
residency scatter parity, checkpoint/resume of the valuation vector,
the truncated-GTG audit on the graded-quality differential config
(fidelity >= the compare_bench gate's default floor), and the GTG
cross-round memo (ROADMAP item 4b).
"""

import dataclasses
import json
import os

import jsonschema
import numpy as np
import pytest

from distributed_learning_simulator_tpu.config import ExperimentConfig
from distributed_learning_simulator_tpu.data.registry import get_dataset
from distributed_learning_simulator_tpu.telemetry.valuation import (
    ClientValuation,
    ValuationState,
    grade_client_labels,
    pearson_corr,
    spearman_corr,
    valuation_record,
)
from distributed_learning_simulator_tpu.utils.reporting import config_hash

_SCHEMA_PATH = os.path.join(
    os.path.dirname(__file__), "data", "metrics_record.schema.json"
)


def _validate_record(record: dict) -> None:
    with open(_SCHEMA_PATH) as f:
        jsonschema.validate(record, json.load(f))


def _tiny(**kw) -> ExperimentConfig:
    base = dict(
        dataset_name="synthetic", model_name="mlp",
        distributed_algorithm="fed", worker_number=6, round=4, epoch=1,
        learning_rate=0.1, batch_size=32, n_train=512, n_test=256,
        log_level="WARNING", dataset_args={"difficulty": 0.5},
        compilation_cache_dir=None,
    )
    base.update(kw)
    return ExperimentConfig(**base)


def _run(config, **kw):
    from distributed_learning_simulator_tpu.simulator import run_simulation

    return run_simulation(config, setup_logging=False, **kw)


# ---- pure host-side arithmetic ---------------------------------------------


def test_scores_hand_computed():
    """cos * norm, non-finite zeroed, unit-L1 normalized — against the
    stats-matrix column layout (STAT_FIELDS order)."""
    import jax.numpy as jnp

    from distributed_learning_simulator_tpu.telemetry.client_stats import (
        STAT_FIELDS,
    )

    cv = ClientValuation()
    n = 3
    stats = np.zeros((n, len(STAT_FIELDS)))
    cols = {name: i for i, name in enumerate(STAT_FIELDS)}
    stats[:, cols["agg_cosine"]] = [0.8, -0.5, np.nan]
    stats[:, cols["update_norm"]] = [2.0, 1.0, 3.0]
    out = np.asarray(cv.scores(jnp.asarray(stats, jnp.float32)))
    raw = np.array([1.6, -0.5, 0.0])  # NaN row zeroed
    expect = raw / np.abs(raw).sum()
    np.testing.assert_allclose(out, expect, rtol=1e-5)


def test_fold_hand_computed_3_client_trace():
    """The exponential-decay fold, scatter semantics included, against a
    hand trace: v <- d*v + (1-d)*loss_delta*score for participants,
    untouched for everyone else."""
    st = ValuationState(3)
    d = 0.5
    # Round 1: all participate, delta 0.1, scores (0.5, 0.3, 0.2).
    st.fold(None, np.array([0.5, 0.3, 0.2]), 0.1, d)
    np.testing.assert_allclose(st.values, [0.025, 0.015, 0.010])
    # Round 2: cohort {0, 2}, delta -0.2 (the round HURT), scores
    # (0.6, 0.4) -> those entries move toward negative credit; client 1
    # keeps its value exactly.
    st.fold(np.array([0, 2]), np.array([0.6, 0.4]), -0.2, d)
    np.testing.assert_allclose(
        st.values,
        [0.5 * 0.025 + 0.5 * (-0.2 * 0.6),
         0.015,
         0.5 * 0.010 + 0.5 * (-0.2 * 0.4)],
    )
    # Round 3: non-finite scores contribute 0, not NaN poison.
    st.fold(np.array([1]), np.array([np.nan]), 0.3, d)
    assert st.values[1] == pytest.approx(0.5 * 0.015)
    assert np.isfinite(st.values).all()


def test_correlations_hand_computed():
    # Perfectly monotonic but non-linear: spearman 1, pearson < 1.
    a = np.array([1.0, 2.0, 3.0, 4.0])
    b = np.array([1.0, 10.0, 100.0, 1000.0])
    assert spearman_corr(a, b) == pytest.approx(1.0)
    assert 0 < pearson_corr(a, b) < 1.0
    # Reversed ranking.
    assert spearman_corr(a, -b) == pytest.approx(-1.0)
    # Ties take average ranks: hand value via the classic formula on
    # ranks [0, 1.5, 1.5, 3] vs [0, 1, 2, 3].
    t = np.array([1.0, 2.0, 2.0, 3.0])
    ra = np.array([0.0, 1.5, 1.5, 3.0])
    rb = np.array([0.0, 1.0, 2.0, 3.0])
    expect = float(np.corrcoef(ra, rb)[0, 1])
    assert spearman_corr(t, a) == pytest.approx(expect)
    # Degenerate inputs -> None, never a crash.
    assert spearman_corr(np.zeros(4), a) is None
    assert pearson_corr(np.array([1.0]), np.array([2.0])) is None
    assert spearman_corr(
        np.array([np.nan, np.nan, 1.0]), np.array([1.0, 2.0, 3.0])
    ) is None


def test_valuation_record_shape_and_cap():
    st = ValuationState(4)
    st.fold(None, np.array([0.4, 0.3, 0.2, 0.1]), 0.5, 0.0)
    rec = valuation_record(st, np.array([0, 1, 2, 3]), 0.5)
    assert rec["n_clients"] == 4 and rec["updated"] == 4
    assert rec["top_clients"][0]["id"] == 0
    assert rec["bottom_clients"][0]["id"] == 3
    assert rec["per_client"]["value"] == [
        pytest.approx(v) for v in (0.2, 0.15, 0.1, 0.05)
    ]
    # Above the cap: no raw per-client dump (metrics.jsonl bloat rule).
    big = ValuationState(64)
    rec = valuation_record(big, None, 0.0)
    assert "per_client" not in rec and rec["updated"] == 64


# ---- off-gate + config-hash invariance -------------------------------------


def test_off_gate_bit_identity_and_records(tiny_dataset):
    """client_valuation='off' with client_stats='on' is the exact PR 4
    program (v3 records, no valuation key); turning valuation ON changes
    records to v7 but must NOT change the training trajectory (the
    scores are a pure extra output of existing intermediates)."""
    import jax

    base = _tiny(client_stats="on")
    off = _run(base, dataset=tiny_dataset)
    on = _run(
        dataclasses.replace(base, client_valuation="on"),
        dataset=tiny_dataset,
    )
    for rec in off["history"]:
        assert rec["schema_version"] == 3
        assert "valuation" not in rec
    for rec in on["history"]:
        assert rec["schema_version"] == 7
        assert rec["valuation"]["n_clients"] == 6
        _validate_record(rec)
    # Bit-identical training history.
    for leaf_off, leaf_on in zip(
        jax.tree_util.tree_leaves(off["global_params"]),
        jax.tree_util.tree_leaves(on["global_params"]),
    ):
        np.testing.assert_array_equal(
            np.asarray(leaf_off), np.asarray(leaf_on)
        )
    accs_off = [r["test_accuracy"] for r in off["history"]]
    accs_on = [r["test_accuracy"] for r in on["history"]]
    assert accs_off == accs_on
    assert off["valuation"] is None and off["valuation_state"] is None
    assert on["valuation_state"] is not None
    assert on["client_valuation"] == "on"


def test_config_hash_off_gate_invariance():
    """Pre-feature configs keep their pre-feature hash: at 'off' every
    valuation knob (and gtg_cross_round_memo=False) drops out of the
    hash, so longitudinal bench comparability survives the feature
    landing; any active setting lands all its knobs."""
    cfg = _tiny()
    h_default = config_hash(cfg)
    # Simulate the pre-feature hash: asdict without the new fields.
    import hashlib

    d = dataclasses.asdict(cfg)
    from distributed_learning_simulator_tpu.utils.reporting import (
        _NON_PROGRAM_FIELDS,
    )

    for k in _NON_PROGRAM_FIELDS + (
        "client_valuation", "valuation_decay", "valuation_audit_every",
        "valuation_audit_permutations", "gtg_cross_round_memo",
        # Off-gated at its 'exact' default like the valuation knobs
        # (ISSUE 10, ops/sampling.py).
        "participation_sampler",
        # Off-gated at their inactive defaults (ISSUE 11, sweep/):
        # persistence knobs sit in _NON_PROGRAM_FIELDS already.
        "sweep_seeds", "sweep_points", "sweep_strategy",
        # Off-gated at 'static' (ISSUE 13, robustness/population.py).
        "population", "population_seed", "join_rate", "depart_rate",
        "drift_fraction", "drift_factor",
        # Off-gated at span_trace='off' (ISSUE 16, telemetry/spans.py).
        "span_trace", "span_buffer_size", "span_flush_last_k",
    ):
        d.pop(k, None)
    pre_feature = hashlib.sha256(
        json.dumps(d, sort_keys=True, default=repr).encode()
    ).hexdigest()[:12]
    assert h_default == pre_feature
    # Off-mode knob tweaks don't move the hash (the program is
    # untouched); activation does, and then every knob lands.
    assert config_hash(
        dataclasses.replace(cfg, valuation_decay=0.5)
    ) == h_default
    on = dataclasses.replace(
        cfg, client_stats="on", client_valuation="on"
    )
    h_on = config_hash(on)
    assert h_on != config_hash(dataclasses.replace(cfg, client_stats="on"))
    assert config_hash(
        dataclasses.replace(on, valuation_decay=0.5)
    ) != h_on
    assert config_hash(
        dataclasses.replace(cfg, gtg_cross_round_memo=True)
    ) != h_default


_AUDIT_OK = dict(client_stats="on", client_valuation="on",
                 valuation_audit_every=2)
VALIDATE_REFUSALS = {
    "client_stats='on'": dict(client_valuation="on"),
    "sign_SGD": dict(distributed_algorithm="sign_SGD", client_stats="on",
                     client_valuation="on"),
    "vmap": dict(execution_mode="threaded", client_stats="on",
                 client_valuation="on"),
    "streaming vector to audit": dict(valuation_audit_every=2),
    "failure injection": dict(failure_mode="dropout", failure_prob=0.5,
                              **_AUDIT_OK),
    # fed_quant's per-chunk upload-quantization keys cannot be replayed
    # exactly on a whole-stack audit.
    "'fed' only": dict(distributed_algorithm="fed_quant", **_AUDIT_OK),
    "reset_client_optimizer": dict(reset_client_optimizer=False,
                                   **_AUDIT_OK),
    "weighted-mean": dict(aggregation="median", **_AUDIT_OK),
    "valuation_decay": dict(valuation_decay=1.0),
}


@pytest.mark.parametrize("cause", sorted(VALIDATE_REFUSALS))
def test_validate_refusals(cause):
    """The audited base validates; each override is refused naming its
    cause."""
    _tiny(**_AUDIT_OK).validate()
    with pytest.raises(ValueError, match=cause):
        _tiny(**VALIDATE_REFUSALS[cause]).validate()


# ---- residency / resume ----------------------------------------------------


def test_streamed_residency_scatter_parity(tiny_dataset):
    """Streamed residency is bit-identical to resident (the PR 7
    contract), so the valuation vector — folded from the same fetched
    scores under participation sampling — must match exactly, and under
    'streamed' it must live IN the host shard store."""
    base = _tiny(
        worker_number=8, participation_fraction=0.5, round=4,
        client_stats="on", client_valuation="on",
    )
    resident = _run(base, dataset=tiny_dataset)
    streamed = _run(
        dataclasses.replace(base, client_residency="streamed"),
        dataset=tiny_dataset,
    )
    v_res = resident["valuation_state"].values
    v_str = streamed["valuation_state"].values
    np.testing.assert_array_equal(v_res, v_str)
    # Sampling at 0.5: some clients were never drawn and sit at exactly
    # 0 — the scatter leaves non-participants untouched.
    assert (v_res != 0).any()
    for rec in streamed["history"]:
        assert rec["schema_version"] == 7
        assert rec["valuation"]["updated"] == 4
    # The store owns the vector under streamed residency.
    assert streamed["valuation_state"]._store is not None
    assert (
        streamed["valuation_state"]._store.valuation
        is streamed["valuation_state"].values
    )


def test_checkpoint_resume_restores_vector(tiny_dataset, tmp_path):
    """A resumed run's valuation vector continues bit-exactly from the
    checkpoint — same contract as every other piece of carried state."""
    ckpt = str(tmp_path / "ckpt")
    base = _tiny(
        round=4, client_stats="on", client_valuation="on",
        checkpoint_dir=ckpt, checkpoint_every=2,
    )
    full = _run(base, dataset=tiny_dataset)
    # Simulate a crash after round 1's checkpoint: wipe the completed
    # run's later checkpoint so resume restarts mid-run from round 1.
    late = os.path.join(ckpt, "round_3.ckpt")
    assert os.path.exists(late)
    os.remove(late)
    resumed = _run(
        dataclasses.replace(base, resume=True), dataset=tiny_dataset,
    )
    np.testing.assert_array_equal(
        full["valuation_state"].values, resumed["valuation_state"].values
    )
    accs_full = [r["test_accuracy"] for r in full["history"]]
    accs_res = [r["test_accuracy"] for r in resumed["history"]]
    assert accs_full[2:] == accs_res


# ---- audit + cross-round memo ----------------------------------------------


def test_audit_fidelity_on_graded_differential():
    """The acceptance differential: a monotonic data-quality gradient
    (grade_client_labels), streaming vector vs cumulative truncated-GTG
    audit SVs — Spearman must clear compare_bench's default
    --valuation-corr-threshold floor (0.8). Also pins the audit's
    schema, its purity (training history identical with audits off),
    and that the valuation ranking itself recovers the gradient."""
    n, rounds = 8, 9
    config = _tiny(
        worker_number=n, round=rounds, n_train=1024, n_test=2048,
        client_stats="on", client_valuation="on",
        valuation_audit_every=2, valuation_audit_permutations=500,
        gtg_eps=1e-4,
    )
    ds = get_dataset(
        "synthetic", n_train=1024, n_test=2048, seed=0, difficulty=0.5
    )
    from distributed_learning_simulator_tpu.simulator import (
        build_client_data,
    )

    cd = build_client_data(config, ds)
    cd.y[:] = grade_client_labels(cd.y, ds.num_classes, seed=1)
    result = _run(config, dataset=ds, client_data=cd)
    audits = [
        r["valuation"]["audit"] for r in result["history"]
        if "audit" in r.get("valuation", {})
    ]
    assert len(audits) == 4  # rounds 2, 4, 6, 8
    assert audits[-1]["audits"] == 4
    last = result["valuation"]["last_audit"]
    assert last["spearman"] >= 0.8
    # Fresh memos by default: no cross-round reuse is reported.
    assert all(a["memo_hit_rate"] is None for a in audits)
    for r in result["history"]:
        _validate_record(r)
    # The streaming ranking itself recovers the quality gradient:
    # cleaner clients (lower index) valued higher.
    v = result["valuation_state"].values
    assert spearman_corr(v, -np.arange(n, dtype=float)) >= 0.9
    # Audit purity: the same run with audits off trains identically.
    no_audit = _run(
        dataclasses.replace(config, valuation_audit_every=0),
        dataset=ds, client_data=cd,
    )
    assert (
        [r["test_accuracy"] for r in no_audit["history"]]
        == [r["test_accuracy"] for r in result["history"]]
    )
    np.testing.assert_array_equal(v, no_audit["valuation_state"].values)


def test_report_run_flagged_overlay():
    """scripts/report_run.py's valuation section: the flagged-client
    overlay pairs each detector-flagged id with its valuation value and
    descending-value rank (jax-free, synthetic records)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "report_run",
        os.path.join(
            os.path.dirname(__file__), "..", "scripts", "report_run.py"
        ),
    )
    rr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rr)
    records = [{
        "round": 1, "test_accuracy": 0.5, "test_loss": 1.0,
        "round_seconds": 0.1, "schema_version": 7,
        "client_stats": {
            "n_clients": 4, "flagged_clients": [2],
            "flag_reason": {"2": "update_norm"}, "quantiles": {},
        },
        "valuation": {
            "n_clients": 4, "updated": 4, "loss_delta": 0.05,
            "top_clients": [{"id": 0, "value": 0.4}],
            "bottom_clients": [{"id": 2, "value": -0.1}],
            "per_client": {
                "client_ids": [0, 1, 2, 3],
                "value": [0.4, 0.2, -0.1, 0.3],
            },
            "audit": {
                "spearman": 0.9, "pearson": 0.8, "spearman_round": 0.9,
                "audits": 1, "permutations": 10, "subset_evals": 20,
                "converged": True, "memo_hit_rate": None, "seconds": 0.2,
            },
        },
    }]
    summary = rr.summarize_run(records)
    overlay = summary["valuation"]["flagged_overlay"]
    assert overlay == [{"id": 2, "value": -0.1, "rank": 3}]
    assert summary["valuation"]["last_audit"]["spearman"] == 0.9
    lines = "\n".join(rr.render_summary(summary))
    assert "flagged client 2" in lines and "GTG audit" in lines


def test_gtg_cross_round_memo(tiny_dataset):
    """ROADMAP item 4b: with gtg_cross_round_memo=True the GTG server
    reuses interior subset utilities across rounds of the same cohort —
    hit rate recorded in the round record and the result dict; the
    default (off) keeps pre-feature records exactly."""
    base = _tiny(
        worker_number=4, round=3,
        distributed_algorithm="GTG_shapley_value",
        round_trunc_threshold=0.0,
    )
    off = _run(base, dataset=tiny_dataset)
    assert off["gtg_memo_hit_rate"] is None
    assert all(
        "gtg_memo_hit_rate" not in r for r in off["history"]
    )
    on = _run(
        dataclasses.replace(base, gtg_cross_round_memo=True),
        dataset=tiny_dataset,
    )
    rates = [
        r["gtg_memo_hit_rate"] for r in on["history"]
        if "gtg_memo_hit_rate" in r
    ]
    # Round 0 has nothing to reuse (rate 0); later rounds walk the same
    # cohort and MUST find seeded interior subsets.
    assert rates and rates[0] == 0.0
    assert max(rates[1:]) > 0.0
    assert on["gtg_memo_hit_rate"] == rates[-1]
    # Same permutation stream either way (the memo changes utilities
    # reused, never the RNG): permutation counts match round 0, where
    # no seeding existed yet.
    assert (
        on["history"][0]["gtg_permutations"]
        == off["history"][0]["gtg_permutations"]
    )
    assert (
        on["history"][0]["gtg_subset_evals"]
        == off["history"][0]["gtg_subset_evals"]
    )
