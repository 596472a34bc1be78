"""Open-world dynamic populations (robustness/population.py, ISSUE 13).

Pins the masked hashed-sampler contract (jit == numpy mirror, departed
never resampled, all-alive == unmasked), the registration stream's
determinism and departure cap, drift's absolute/idempotent schedule,
HostShardStore append-growth, the static off-gate (config_hash + history
invariance), the bit-identical-until-first-join acceptance differential,
quorum-rejection under churn (rejected_by_churn), the 10x-growth run
with schema-v9 records, the streaming-valuation drift-tracking floor
(Spearman >= 0.8 against the planted grades), refusal causes, the
vmapped-sweep blocker, and report_run's population section.
"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import jsonschema
import numpy as np
import pytest

from distributed_learning_simulator_tpu.config import ExperimentConfig
from distributed_learning_simulator_tpu.data.residency import HostShardStore
from distributed_learning_simulator_tpu.ops.sampling import (
    hashed_cohort,
    hashed_cohort_np,
)
from distributed_learning_simulator_tpu.robustness.population import (
    PopulationModel,
    pop_key_words,
)
from distributed_learning_simulator_tpu.telemetry.valuation import (
    spearman_corr,
)
from distributed_learning_simulator_tpu.utils.reporting import config_hash

_SCHEMA_PATH = os.path.join(
    os.path.dirname(__file__), "data", "metrics_record.schema.json"
)


def _validate_record(record: dict) -> None:
    with open(_SCHEMA_PATH) as f:
        jsonschema.validate(record, json.load(f))


def _dyn(**kw) -> ExperimentConfig:
    base = dict(
        dataset_name="synthetic", model_name="mlp",
        distributed_algorithm="fed", worker_number=8, round=5, epoch=1,
        learning_rate=0.1, batch_size=32, n_train=512, n_test=256,
        log_level="WARNING", dataset_args={"difficulty": 0.5},
        participation_fraction=0.5, participation_sampler="hashed",
        client_residency="streamed", compilation_cache_dir=None,
        population="dynamic",
    )
    base.update(kw)
    return ExperimentConfig(**base)


def _run(config, **kw):
    from distributed_learning_simulator_tpu.simulator import run_simulation

    return run_simulation(config, setup_logging=False, **kw)


# ---- masked hashed sampler (ops/sampling.py) -------------------------------


def test_masked_hashed_draw_jit_equals_numpy():
    words = np.asarray(
        jax.random.key_data(jax.random.key(7))
    ).ravel()
    key = jax.random.wrap_key_data(jnp.asarray(words))
    rng = np.random.default_rng(3)
    for n, k in ((37, 9), (100, 25), (64, 16)):
        alive = np.ones(n, dtype=bool)
        alive[rng.choice(n, size=n // 3, replace=False)] = False
        got_np = hashed_cohort_np(words, n, k, alive=alive)
        got_jit = np.asarray(
            jax.jit(
                lambda kk, a, _n=n, _k=k: hashed_cohort(kk, _n, _k, alive=a)
            )(key, jnp.asarray(alive))
        )
        np.testing.assert_array_equal(got_np, got_jit)
        # Departed indices are never sampled; the cohort is duplicate-free.
        assert alive[got_np].all()
        assert len(set(got_np.tolist())) == k


def test_all_alive_mask_equals_unmasked_draw():
    """The static-until-first-event bit-identity contract: an all-True
    mask only adds rejections that never fire, so the selection is the
    unmasked draw element-for-element."""
    words = np.asarray(
        jax.random.key_data(jax.random.key(11))
    ).ravel()
    for n, k in ((50, 10), (128, 32)):
        np.testing.assert_array_equal(
            hashed_cohort_np(words, n, k),
            hashed_cohort_np(words, n, k, alive=np.ones(n, dtype=bool)),
        )


def test_masked_draw_errors():
    words = np.asarray(
        jax.random.key_data(jax.random.key(0))
    ).ravel()
    with pytest.raises(ValueError, match="alive"):
        hashed_cohort_np(
            words, 10, 5, alive=np.zeros(10, dtype=bool)
        )
    # The jitted path refuses a concrete infeasible mask too — the
    # fixed-shape while_loop would otherwise spin forever on device.
    with pytest.raises(ValueError, match="alive"):
        hashed_cohort(
            jax.random.key(0), 10, 5, alive=np.zeros(10, dtype=bool)
        )
    from distributed_learning_simulator_tpu.ops.sampling import (
        draw_cohort_host,
    )

    with pytest.raises(ValueError, match="exact"):
        draw_cohort_host(
            jax.random.key(0), 10, 5, "exact",
            alive=np.ones(10, dtype=bool),
        )


# ---- registration stream (PopulationModel) ---------------------------------


def _model(n=10, cohort=4, **kw):
    cfg = _dyn(worker_number=n, **kw)
    return PopulationModel.from_config(cfg, n, cohort)


def test_event_stream_deterministic_and_decoupled():
    pm = _model(join_rate=1.5, depart_rate=0.3)
    key = jax.random.key(42)
    words = pop_key_words(key, pm.seed)
    e1 = pm.draw_events(words, 3)
    e2 = pm.draw_events(words, 3)
    assert e1.joins == e2.joins
    np.testing.assert_array_equal(e1.departs, e2.departs)
    assert e1.joins in (1, 2)  # floor(1.5) + bernoulli(0.5)
    # A different population_seed re-rolls the events (the fold_in
    # stream), without touching any other round-key consumer.
    pm2 = _model(join_rate=1.5, depart_rate=0.3, population_seed=9)
    words2 = pop_key_words(key, pm2.seed)
    assert not np.array_equal(words, words2)


def test_departure_cap_keeps_cohort_fillable():
    """Departures never push the alive population below the pinned
    cohort size (the sampler must fill k slots); excess draws drop in
    index order — deterministic."""
    pm = _model(n=6, cohort=4, depart_rate=0.999)
    words = pop_key_words(jax.random.key(1), pm.seed)
    ev = pm.draw_events(words, 0)
    assert ev.departs.size <= 6 - 4
    store = _store(6)
    pm.apply(ev, store)
    assert int(pm.alive.sum()) >= 4
    # Never resampled: a second round's draw can only depart ALIVE ids.
    ev2 = pm.draw_events(pop_key_words(jax.random.key(2), pm.seed), 1)
    assert not np.isin(ev2.departs, ev.departs).any()


def _store(n, slots=4, dim=3, state=None):
    return HostShardStore(
        np.arange(n * slots * dim, dtype=np.float32).reshape(
            n, slots, dim
        ),
        np.zeros((n, slots), dtype=np.int32),
        np.ones((n, slots), dtype=np.float32),
        np.full(n, float(slots), dtype=np.float32),
        state=state,
    )


def test_store_grow_appends_without_touching_resident_rows():
    store = _store(4)
    before = np.array(store.x, copy=True)
    first = store.grow(
        np.ones((2, 4, 3), np.float32), np.ones((2, 4), np.int32),
        np.ones((2, 4), np.float32), np.full(2, 4.0, np.float32),
    )
    assert first == 4 and store.n_clients == 6
    np.testing.assert_array_equal(store.x[:4], before)
    np.testing.assert_array_equal(store.x[4:], np.ones((2, 4, 3)))
    # Gather/scatter index math covers the grown rows.
    x, y, m, s = store.gather_data(np.array([0, 5]))
    assert x.shape[0] == 2 and s[1] == 4.0
    # Repeated growth amortizes through the capacity-doubling backing.
    for _ in range(5):
        store.grow(
            np.zeros((3, 4, 3), np.float32), np.zeros((3, 4), np.int32),
            np.ones((3, 4), np.float32), np.full(3, 4.0, np.float32),
        )
    assert store.n_clients == 21
    np.testing.assert_array_equal(store.x[:4], before)
    # The attached valuation vector grows with zeros.
    store2 = _store(3)
    store2.attach_valuation(np.array([1.0, 2.0, 3.0]))
    store2.grow(
        np.zeros((2, 4, 3), np.float32), np.zeros((2, 4), np.int32),
        np.ones((2, 4), np.float32), np.full(2, 4.0, np.float32),
    )
    np.testing.assert_array_equal(
        store2.valuation, [1.0, 2.0, 3.0, 0.0, 0.0]
    )
    # A leaf REPLACED between grows (attach_valuation on resume) must
    # not resurrect stale backing rows on the next grow.
    store2.attach_valuation(np.array([9.0, 8.0, 7.0, 6.0, 5.0]))
    store2.grow(
        np.zeros((1, 4, 3), np.float32), np.zeros((1, 4), np.int32),
        np.ones((1, 4), np.float32), np.full(1, 4.0, np.float32),
    )
    np.testing.assert_array_equal(
        store2.valuation, [9.0, 8.0, 7.0, 6.0, 5.0, 0.0]
    )
    # Stateful stores require state rows for the joiners.
    store3 = _store(2, state={"m": np.zeros((2, 5), np.float32)})
    with pytest.raises(ValueError, match="state_rows"):
        store3.grow(
            np.zeros((1, 4, 3), np.float32), np.zeros((1, 4), np.int32),
            np.ones((1, 4), np.float32), np.full(1, 4.0, np.float32),
        )
    store3.grow(
        np.zeros((1, 4, 3), np.float32), np.zeros((1, 4), np.int32),
        np.ones((1, 4), np.float32), np.full(1, 4.0, np.float32),
        state_rows={"m": np.ones((1, 5), np.float32)},
    )
    assert store3.state["m"].shape == (3, 5)


def test_drift_schedule_absolute_and_idempotent():
    """Drift corruption is an absolute per-round level (fixed slot order
    + fixed noise labels): re-applying any level is idempotent, levels
    are monotone in the round, and the final level matches the planted
    grade — the property resume-exactness rests on."""
    pm = _model(n=6, cohort=3, drift_fraction=1.0, drift_factor=0.9,
                round=8)
    store = _store(6, slots=8)
    store.y[:] = 7  # uniform original labels; noise shows as != 7
    pm._num_classes = 5
    pm.apply_drift(store, 7)  # final round -> peak level
    final = np.array(store.y, copy=True)
    corrupted = (final != 7).sum(axis=1)
    # Peak corruption ~ grade * slots, monotone across the graded ranks.
    grades_by_client = np.zeros(6)
    grades_by_client[pm.drift_ids] = pm.drift_grades
    assert spearman_corr(corrupted, grades_by_client) > 0.99
    # Earlier rounds corrupt a NESTED PREFIX of the same slots.
    pm2 = _model(n=6, cohort=3, drift_fraction=1.0, drift_factor=0.9,
                 round=8)
    pm2._num_classes = 5
    store2 = _store(6, slots=8)
    store2.y[:] = 7
    pm2.apply_drift(store2, 3)
    mid = np.array(store2.y, copy=True)
    assert ((mid != 7) <= (final != 7)).all()
    # Idempotent: applying the same level twice changes nothing.
    pm2.apply_drift(store2, 3)
    np.testing.assert_array_equal(store2.y, mid)
    # And applying the final level on top reaches the same state as the
    # fresh model did (absolute, not incremental).
    pm2.apply_drift(store2, 7)
    np.testing.assert_array_equal(store2.y, final)


# ---- config refusals / off-gate --------------------------------------------


VALIDATE_REFUSALS = {
    "streamed": dict(client_residency="resident"),
    "hashed": dict(participation_sampler="exact"),
    "participation_fraction": dict(participation_fraction=1.0),
    "speed": dict(async_mode="on", arrival_model="bimodal"),
    "FedAvg": dict(distributed_algorithm="sign_SGD"),
    "cohort": dict(distributed_algorithm="GTG_shapley_value"),
    "thread": dict(execution_mode="threaded"),
    "audit": dict(client_stats="on", client_valuation="on",
                  valuation_audit_every=2),
}


@pytest.mark.parametrize("cause", sorted(VALIDATE_REFUSALS))
def test_validate_refusal_causes(cause):
    _dyn().validate()  # the composed base is legal
    with pytest.raises(ValueError, match=cause):
        _dyn(**VALIDATE_REFUSALS[cause]).validate()


def test_static_offgate_hash_and_history(tiny_dataset):
    """population='static' is the exact pre-feature path: the hash drops
    every population knob at the static default, and off-mode knob
    tweaks change nothing about the run."""
    base = _dyn(population="static")
    assert config_hash(base) == config_hash(
        dataclasses.replace(
            base, population_seed=5, join_rate=3.0, depart_rate=0.2,
            drift_fraction=0.4, drift_factor=0.9,
        )
    )
    assert config_hash(base) != config_hash(
        dataclasses.replace(base, population="dynamic")
    )
    r1 = _run(base, dataset=tiny_dataset)
    r2 = _run(
        dataclasses.replace(base, population_seed=5, join_rate=3.0),
        dataset=tiny_dataset,
    )
    assert [h["test_accuracy"] for h in r1["history"]] == [
        h["test_accuracy"] for h in r2["history"]
    ]
    assert [h["cohort_hash"] for h in r1["history"]] == [
        h["cohort_hash"] for h in r2["history"]
    ]
    assert r1["population_summary"] is None


def test_sweep_vmapped_refuses_dynamic_and_auto_schedules():
    from distributed_learning_simulator_tpu.sweep.spec import SweepSpec

    cfg = _dyn(sweep_seeds="0,1", sweep_strategy="vmapped")
    spec = SweepSpec.from_config(cfg)
    with pytest.raises(ValueError, match="fixed N"):
        spec.validate()
    auto = SweepSpec.from_config(
        dataclasses.replace(cfg, sweep_strategy="auto")
    )
    assert auto.resolve_strategy() == "scheduled"
    ok, reason = auto.fleet_compatible()
    assert not ok and "population='dynamic'" in reason


# ---- integration -----------------------------------------------------------


def test_dynamic_bit_identical_to_static_until_first_join(tiny_dataset):
    """The acceptance differential's first half: with join-only churn
    (one join per round, applied at the round boundary), the dynamic
    run's round 0 — metrics AND cohort hash — is bit-identical to the
    static run; later rounds diverge because the hashed draw's index
    space grew."""
    static = _run(
        _dyn(population="static"), dataset=tiny_dataset
    )
    dyn = _run(_dyn(join_rate=1.0), dataset=tiny_dataset)
    s0, d0 = static["history"][0], dyn["history"][0]
    for key in ("test_accuracy", "test_loss", "mean_client_loss",
                "cohort_hash"):
        assert s0[key] == d0[key], key
    # Divergence after the first join is REAL (the draw covers a grown
    # index space) — identical tails would mean the mask/space is dead.
    assert [h["cohort_hash"] for h in static["history"][1:]] != [
        h["cohort_hash"] for h in dyn["history"][1:]
    ]
    assert dyn["population_summary"]["joins_total"] == len(
        dyn["history"]
    )


def test_tenx_growth_run_records_and_summary(tiny_dataset):
    """A 10x population-growth run: every record validates against the
    checked-in v9 schema, joined clients enter cohorts, and the summary
    books the growth."""
    n0, rounds = 8, 6
    cfg = _dyn(
        round=rounds, join_rate=float(round(9 * n0 / rounds)),
        depart_rate=0.05, drift_fraction=0.25, drift_factor=0.8,
    )
    result = _run(cfg, dataset=tiny_dataset)
    summary = result["population_summary"]
    assert summary["n_registered"] == n0 + summary["joins_total"]
    assert summary["growth_ratio"] >= 9.0
    participants = set()
    for r in result["history"]:
        assert r["schema_version"] == 9
        _validate_record(r)
        p = r["population"]
        assert p["n_alive"] <= p["n_registered"]
        participants.add(r["cohort_hash"])
    # The grown index space is actually sampled: cohort hashes differ
    # every round (a frozen index space would repeat only by chance,
    # but never under growth — n changes the whole stream).
    assert len(participants) == rounds
    # Mid-growth state survives the result surface for library callers.
    assert result["client_state"] is None  # stateless default


def test_churn_quorum_rejection_flagged(tiny_dataset):
    """Departures colliding with the quorum floor: a round whose
    survivors fall below min_survivors after mid-round departures is
    rejected in-program (previous global retained — the PR 2 contract)
    and its record carries rejected_by_churn."""
    cfg = _dyn(depart_rate=0.6, min_survivors=4)
    result = _run(cfg, dataset=tiny_dataset)
    assert result["rounds_rejected"] >= 1
    flagged = [
        r for r in result["history"]
        if r["population"]["rejected_by_churn"]
    ]
    assert flagged
    for r in flagged:
        assert r["round_rejected"] is True
        assert r["population"]["cohort_departs"] > 0
        _validate_record(r)
    assert (
        result["population_summary"]["rounds_rejected_by_churn"]
        == len(flagged)
    )


def test_valuation_tracks_drifting_cohort_through_churn():
    """The acceptance differential's second half: the PR 9 streaming
    valuation tracks the planted drifting-quality cohort THROUGH churn
    (joins + departures active) — Spearman >= 0.8 between the final
    valuation of the startup population and the negated planted grades
    (the compare_bench fidelity floor)."""
    n, rounds = 12, 20
    cfg = _dyn(
        worker_number=n, round=rounds, n_train=1024, n_test=512,
        participation_fraction=0.75,
        client_stats="on", client_valuation="on",
        join_rate=0.5, depart_rate=0.03,
        drift_fraction=1.0, drift_factor=0.9,
    )
    result = _run(cfg)
    v = result["valuation_state"].values
    pm = PopulationModel.from_config(cfg, n, cfg.cohort_size(n))
    grades = np.zeros(n)
    grades[pm.drift_ids] = pm.drift_grades
    sp = spearman_corr(v[:n], -grades)
    assert sp is not None and sp >= 0.8, sp
    # Valued ids stay TRUE indices across growth: the vector covers the
    # grown population and joiners accumulated their own evidence.
    assert v.shape[0] == result["population_summary"]["n_registered"]
    assert v.shape[0] > n


def test_dynamic_run_does_not_mutate_caller_client_data(tiny_dataset):
    """Drift mutates label rows in place, and the store normally aliases
    the caller's packed arrays — a dynamic run must take ownership of
    the labels so a shared client_data (bench legs run several legs on
    one packed set) is never corrupted as a side effect."""
    from distributed_learning_simulator_tpu.simulator import (
        build_client_data,
    )

    cfg = _dyn(join_rate=1.0, drift_fraction=0.5, drift_factor=0.9)
    cd = build_client_data(cfg, tiny_dataset)
    y_before = np.array(cd.y, copy=True)
    x_before = np.array(cd.x, copy=True)
    _run(cfg, dataset=tiny_dataset, client_data=cd)
    np.testing.assert_array_equal(cd.y, y_before)
    np.testing.assert_array_equal(cd.x, x_before)
    assert cd.n_clients == 8  # growth never leaks into the caller


def test_report_run_population_section(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "report_run", os.path.join(
            os.path.dirname(__file__), "..", "scripts", "report_run.py"
        )
    )
    rr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rr)
    records = []
    for i in range(4):
        records.append({
            "round": i, "test_accuracy": 0.5 + 0.1 * i, "test_loss": 1.0,
            "mean_client_loss": 1.1, "round_seconds": 0.2,
            "schema_version": 9,
            "round_rejected": i == 2,
            "population": {
                "n_initial": 8,
                "n_registered": 8 + 2 * (i + 1), "n_alive": 7 + 2 * i,
                "joins": 2, "departs": 1 if i else 0,
                "cohort_departs": 1 if i == 2 else 0,
                "drift_cohort_size": 2, "drift_clients": [1, 5],
                "rejected_by_churn": i == 2,
            },
            "valuation": {
                "n_clients": 8, "updated": 4, "loss_delta": 0.01,
                "top_clients": [{"id": 0, "value": 0.5}],
                "bottom_clients": [{"id": 5, "value": -0.4},
                                   {"id": 1, "value": -0.2}],
            },
        })
    summary = rr.summarize_run(records)
    p = summary["population"]
    assert p["n_initial"] == 8
    assert p["n_registered_final"] == 16
    assert p["joins_total"] == 8 and p["departs_total"] == 3
    assert p["churn_rejected_rounds"] == [2]
    assert p["drift_clients"] == [1, 5]
    ov = summary["valuation"]["drift_overlay"]
    assert ov["drift_in_bottom"] == [5, 1]
    assert ov["drift_in_top"] == []
    text = "\n".join(rr.render_summary(summary))
    assert "dynamic population: 8 -> 16" in text
    assert "rejected by churn" in text
    assert "drift overlay: 2/2" in text
