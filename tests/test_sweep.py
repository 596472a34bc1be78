"""Sweep engine (sweep/): fleets, scheduling, refusals, resume.

The load-bearing contracts (ISSUE 11):

* a vmapped fleet point's metric history is BIT-identical to a solo
  ``run_simulation`` with that seed on the shared data (including the
  in-program cohort draw — cohort_hash matches);
* points are RNG-independent: a point's history does not depend on who
  else is in the fleet;
* the scheduler groups by config_hash but caches programs under the
  seed-normalized program key, so seed-varied groups share ONE compiled
  program — and its lean warm-program loop reproduces run_simulation
  bit-for-bit;
* non-sweepable features refuse with causes;
* an interrupted sweep resumes from sweep_dir and stitches
  bit-identically.
"""

import dataclasses
import json
import os

import jsonschema
import pytest

from distributed_learning_simulator_tpu.config import ExperimentConfig
from distributed_learning_simulator_tpu.data.registry import get_dataset
from distributed_learning_simulator_tpu.simulator import (
    build_client_data,
    run_simulation,
)
from distributed_learning_simulator_tpu.sweep import (
    SweepScheduler,
    SweepSpec,
    run_sweep,
)
from distributed_learning_simulator_tpu.utils.reporting import config_hash

#: The metric fields the bit-identity contract covers (round_seconds is
#: wall-clock and legitimately differs; cohort_hash pins the sampled
#: cohort stream).
_KEYS = ("test_accuracy", "test_loss", "mean_client_loss", "cohort_hash")


def _base(**overrides) -> ExperimentConfig:
    kw = dict(
        dataset_name="synthetic",
        model_name="mlp",
        distributed_algorithm="fed",
        worker_number=8,
        round=3,
        epoch=1,
        learning_rate=0.1,
        batch_size=16,
        n_train=256,
        n_test=128,
        log_level="WARNING",
        dataset_args={"difficulty": 0.5},
        participation_fraction=0.5,
        compilation_cache_dir=None,
    )
    kw.update(overrides)
    return ExperimentConfig(**kw)


@pytest.fixture(scope="module")
def shared():
    base = _base()
    ds = get_dataset("synthetic", n_train=256, n_test=128, seed=base.seed,
                     difficulty=0.5)
    cd = build_client_data(base, ds)
    return base, ds, cd


def _solo(base, ds, cd, **overrides):
    cfg = dataclasses.replace(base, **overrides)
    return run_simulation(cfg, dataset=ds, client_data=cd,
                          setup_logging=False)["history"]


def _assert_history_equal(a, b, context=""):
    assert len(a) == len(b), context
    for ra, rb in zip(a, b):
        for k in _KEYS:
            assert ra.get(k) == rb.get(k), (context, k, ra, rb)


def test_fleet_bit_identical_to_solo_and_v8_records(shared, tmp_path):
    """The acceptance pin: a vmapped seed fleet reproduces each seed's
    solo history bit-for-bit (incl. the sampled-cohort stream), pays
    ONE compile for the whole fleet, and writes valid schema-v8
    records."""
    base, ds, cd = shared
    seeds = [0, 1, 2]
    spec = SweepSpec(base, [{"seed": s} for s in seeds],
                     strategy="vmapped", sweep_dir=str(tmp_path))
    out = run_sweep(spec, dataset=ds, client_data=cd)
    assert out["strategy"] == "vmapped"
    assert out["programs_compiled"] == 1
    assert out["compile_reuse_fraction"] == pytest.approx(2 / 3)
    for p in out["points"]:
        solo = _solo(base, ds, cd, seed=p["seed"])
        _assert_history_equal(solo, p["history"], f"seed {p['seed']}")
    # The winner is the argmax final accuracy over the points.
    finals = [p["final_accuracy"] for p in out["points"]]
    assert out["winner"]["final_accuracy"] == max(finals)
    # Persisted records validate against the checked-in v8 schema.
    schema_path = os.path.join(
        os.path.dirname(__file__), "data", "metrics_record.schema.json"
    )
    with open(schema_path) as f:
        schema = json.load(f)
    with open(os.path.join(str(tmp_path), "metrics.jsonl")) as f:
        records = [json.loads(line) for line in f if line.strip()]
    assert len(records) == len(seeds) * base.round
    for rec in records:
        assert rec["schema_version"] == 8
        assert rec["sweep"]["strategy"] == "vmapped"
        assert rec["sweep"]["experiments"] == len(seeds)
        jsonschema.validate(rec, schema)
    # compile_reused accounting: point 0 carries the fleet's compile.
    assert [p["compile_reused"] for p in out["points"]] == [
        False, True, True,
    ]


def test_fleet_point_independence(shared):
    """A point's history must not depend on who else is in the fleet —
    the property sweep-level resume (re-running only missing points)
    rests on."""
    base, ds, cd = shared
    small = dataclasses.replace(base, round=2)
    out_a = run_sweep(
        SweepSpec(small, [{"seed": 0}, {"seed": 1}], strategy="vmapped"),
        dataset=ds, client_data=cd,
    )
    out_b = run_sweep(
        SweepSpec(small, [{"seed": 0}, {"seed": 5}], strategy="vmapped"),
        dataset=ds, client_data=cd,
    )
    _assert_history_equal(
        out_a["points"][0]["history"], out_b["points"][0]["history"],
        "fleet composition changed point 0",
    )


def test_fleet_lr_axis(shared):
    """learning_rate is a fleet axis: lr-varied points run in one
    program as a length-E factor vector. The base-lr point (factor
    exactly 1.0) stays bit-identical to its solo run; the varied point
    genuinely trains at a different rate."""
    base, ds, cd = shared
    small = dataclasses.replace(base, round=2)
    out = run_sweep(
        SweepSpec(
            small,
            [{"learning_rate": 0.1}, {"learning_rate": 0.05}],
            strategy="vmapped",
        ),
        dataset=ds, client_data=cd,
    )
    solo = _solo(small, ds, cd, learning_rate=0.1)
    _assert_history_equal(solo, out["points"][0]["history"], "base-lr")
    assert (
        out["points"][0]["history"][-1]["test_loss"]
        != out["points"][1]["history"][-1]["test_loss"]
    )


def test_fleet_mesh_packing(shared):
    """Experiment-axis mesh packing: E experiments sharded over the mesh
    (each device owns whole experiments) keep every RNG stream exact —
    cohort hashes bit-match the solo runs — while metric VALUES agree to
    reduction-order tolerance: the SPMD partitioner may re-associate
    intra-experiment reductions, the same documented contract as
    resident-vs-mesh fed runs (PR 10, docs/ROBUSTNESS.md)."""
    base, ds, cd = shared
    meshed = dataclasses.replace(base, round=2, mesh_devices=2)
    out = run_sweep(
        SweepSpec(meshed, [{"seed": 0}, {"seed": 1}], strategy="vmapped"),
        dataset=ds, client_data=cd,
    )
    for p in out["points"]:
        solo = _solo(base, ds, cd, seed=p["seed"], round=2)
        assert len(solo) == len(p["history"])
        for rs, rf in zip(solo, p["history"]):
            assert rs["cohort_hash"] == rf["cohort_hash"]
            for k in ("test_accuracy", "test_loss", "mean_client_loss"):
                assert rs[k] == pytest.approx(rf[k], rel=1e-5), (
                    p["seed"], k,
                )


def test_scheduled_grouping_reuse_and_bit_identity(shared):
    """The 2-hash sweep: seeds x horizons give two distinct config
    hashes but ONE seed-normalized program — the scheduler compiles
    once, every later point rides it warm, and the lean loop's
    histories equal run_simulation's bit-for-bit."""
    base, ds, cd = shared
    points = [
        {"seed": s, "round": r} for s in (0, 1) for r in (2, 3)
    ]
    out = run_sweep(
        SweepSpec(base, points, strategy="scheduled"),
        dataset=ds, client_data=cd,
    )
    assert out["strategy"] == "scheduled"
    hashes = {p["config_hash"] for p in out["points"]}
    assert len(hashes) == 2  # seed in the hash, round not
    assert out["programs_compiled"] == 1
    assert out["compile_reuse_fraction"] == 0.75
    assert [p["compile_reused"] for p in out["points"]] == [
        False, True, True, True,
    ]
    for p in out["points"]:
        solo = _solo(base, ds, cd, seed=p["seed"], round=p["rounds"])
        _assert_history_equal(solo, p["history"], f"point {p['index']}")


def test_auto_strategy_resolution(shared):
    """'auto' picks the fleet when every point is fleet-compatible and
    falls back to the scheduler (with the blocking feature nameable)
    when not."""
    base, _, _ = shared
    fleet = SweepSpec(base, [{"seed": 0}, {"seed": 1}]).validate()
    assert fleet.resolve_strategy() == "vmapped"
    mixed = SweepSpec(
        base, [{"seed": 0}, {"batch_size": 32}]
    ).validate()
    ok, reason = mixed.fleet_compatible()
    assert not ok and "batch_size" in reason
    assert mixed.resolve_strategy() == "scheduled"


# cause -> what, built on the shared base config, must be refused.
REFUSALS = {
    # Threaded oracle: no shared program to warm.
    "threaded": lambda base: dataclasses.replace(
        base, execution_mode="threaded", sweep_seeds="0,1"),
    # Shapley: post_round must observe every round synchronously.
    "post_round": lambda base: dataclasses.replace(
        base, distributed_algorithm="GTG_shapley_value",
        sweep_seeds="0,1"),
    # Forcing 'vmapped' on a non-fleet feature names the blocker.
    "client_stats": lambda base: SweepSpec(
        dataclasses.replace(base, client_stats="on"),
        [{"seed": 0}, {"seed": 1}], strategy="vmapped"),
    # Duplicate points are refused, not silently recomputed.
    "identical": lambda base: SweepSpec(base, [{"seed": 3}, {"seed": 3}]),
    # sweep_resume without a sweep_dir to resume from.
    "sweep_dir": lambda base: dataclasses.replace(
        base, sweep_seeds="0,1", sweep_resume=True),
}


@pytest.mark.parametrize("cause", sorted(REFUSALS))
def test_refusal_causes(shared, cause):
    base, _, _ = shared
    with pytest.raises(ValueError, match=cause):
        REFUSALS[cause](base).validate()


def test_sweep_resume_bit_identical(shared, tmp_path):
    """Chaos-crash after 2 points, then resume: the persisted points
    load (not re-executed), the remainder runs, and the stitched sweep
    equals the uninterrupted one bit-for-bit."""
    base, ds, cd = shared
    small = dataclasses.replace(base, round=2)
    points = [{"seed": s} for s in range(4)]
    sweep_dir = str(tmp_path / "sweep")
    os.environ["DLS_SWEEP_CRASH_AFTER"] = "2"
    try:
        with pytest.raises(RuntimeError, match="chaos"):
            run_sweep(
                SweepSpec(small, points, strategy="scheduled",
                          sweep_dir=sweep_dir),
                dataset=ds, client_data=cd,
            )
    finally:
        del os.environ["DLS_SWEEP_CRASH_AFTER"]
    resumed = run_sweep(
        SweepSpec(small, points, strategy="scheduled",
                  sweep_dir=sweep_dir, resume=True),
        dataset=ds, client_data=cd,
    )
    assert resumed["resumed_points"] == 2
    assert resumed["executed_points"] == 2
    assert [p["resumed"] for p in resumed["points"]] == [
        True, True, False, False,
    ]
    reference = run_sweep(
        SweepSpec(small, points, strategy="scheduled"),
        dataset=ds, client_data=cd,
    )
    for pr, pf in zip(reference["points"], resumed["points"]):
        _assert_history_equal(
            pr["history"], pf["history"], f"resumed point {pr['index']}"
        )


def test_scheduler_reusable_outside_sweeps(shared):
    """The warm-program cache is a standalone tool (bench.py routes its
    same-program legs through one): two configs differing only in seed
    and horizon share a program, and the second run reports the
    reuse."""
    base, ds, cd = shared
    sched = SweepScheduler()
    r1 = sched.run(dataclasses.replace(base, round=2),
                   dataset=ds, client_data=cd)
    r2 = sched.run(dataclasses.replace(base, seed=9, round=3),
                   dataset=ds, client_data=cd)
    assert r1["compile_reused"] is False
    assert r2["compile_reused"] is True
    assert sched.programs_compiled == 1
    _assert_history_equal(
        _solo(base, ds, cd, seed=9, round=3), r2["history"],
        "scheduler lean loop",
    )


def test_sweep_knobs_offgate_config_hash(shared):
    """Sweep knobs drop out of config_hash at their off values (the
    PR 9/10 off-gate discipline): persistence knobs never hash, an
    ACTIVE sweep does."""
    base, _, _ = shared
    assert config_hash(base) == config_hash(
        dataclasses.replace(base, sweep_dir="/tmp/x", sweep_resume=True)
    )
    assert config_hash(base) != config_hash(
        dataclasses.replace(base, sweep_seeds="0,1")
    )
    # Point configs strip the sweep knobs: a point's hash equals the
    # standalone config's hash (the scheduler-grouping comparability).
    spec = SweepSpec.from_config(
        dataclasses.replace(base, sweep_seeds="0,5")
    )
    assert config_hash(spec.points[1].config) == config_hash(
        dataclasses.replace(base, seed=5)
    )


def test_from_config_grid(shared):
    """sweep_seeds x sweep_points build the grid; JSON parsing covers
    the CLI path."""
    base, _, _ = shared
    spec = SweepSpec.from_config(dataclasses.replace(
        base, sweep_seeds="0,1",
        sweep_points='[{"learning_rate": 0.1}, {"learning_rate": 0.05}]',
    ))
    assert len(spec.points) == 4
    assert {(p.config.seed, p.config.learning_rate)
            for p in spec.points} == {
        (0, 0.1), (1, 0.1), (0, 0.05), (1, 0.05),
    }
    with pytest.raises(ValueError, match="override"):
        SweepSpec.from_config(
            dataclasses.replace(base, sweep_points='{"not": "a list"}')
        )
