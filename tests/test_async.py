"""Asynchronous federation (config.async_mode; robustness/arrivals.py).

The determinism contracts this file pins:

* ``async_mode='off'`` (the default) never constructs the machinery
  (``AsyncFederation.from_config`` is None even with arrival knobs set).
* The COMPILED async program at ``round_deadline=inf`` is bit-identical
  to synchronous FedAvg — participation sampling, failure draws, quorum
  verdicts, and cohort hashes included (the degenerate-equivalence
  contract).
* The staleness discount and the buffer insert/trigger/apply math match
  a hand-computed 3-client trace.
* Checkpoint/resume replays the buffer bit-exactly; config/checkpoint
  async mismatches are refused with the cause.
* sign_SGD, the Shapley servers, and the threaded oracle refuse
  ``async_mode='on'`` with a single-line error naming the flag.
"""

import dataclasses
import glob
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_learning_simulator_tpu.config import ExperimentConfig
from distributed_learning_simulator_tpu.robustness.arrivals import (
    AsyncFederation,
    staleness_discount,
)
from distributed_learning_simulator_tpu.simulator import run_simulation


def _run(cfg, **overrides):
    cfg = dataclasses.replace(cfg, **overrides)
    return run_simulation(cfg, setup_logging=False)


def _series(result, *keys):
    return {k: [h.get(k) for h in result["history"]] for k in keys}


_ASYNC_ON = dict(
    async_mode="on", arrival_model="bimodal", arrival_slow_fraction=0.4,
    arrival_slow_factor=8.0, round_deadline=1.5, async_buffer_size=3,
    staleness_alpha=0.5,
)


# ------------------------------------------------------------- validation


def test_config_validation():
    ExperimentConfig(**_ASYNC_ON).validate()
    with pytest.raises(ValueError, match="async_mode"):
        ExperimentConfig(async_mode="sometimes").validate()
    with pytest.raises(ValueError, match="arrival_model"):
        ExperimentConfig(
            async_mode="on", arrival_model="gaussian"
        ).validate()
    with pytest.raises(ValueError, match="arrival_model"):
        ExperimentConfig(async_mode="on").validate()  # none + on
    with pytest.raises(ValueError, match="round_deadline"):
        ExperimentConfig(
            async_mode="on", arrival_model="bimodal", round_deadline=0.0
        ).validate()
    with pytest.raises(ValueError, match="async_buffer_size"):
        ExperimentConfig(
            async_mode="on", arrival_model="bimodal", async_buffer_size=0
        ).validate()
    with pytest.raises(ValueError, match="staleness_alpha"):
        ExperimentConfig(
            async_mode="on", arrival_model="bimodal", staleness_alpha=-0.1
        ).validate()
    with pytest.raises(ValueError, match="arrival_slow_fraction"):
        ExperimentConfig(
            async_mode="on", arrival_model="bimodal",
            arrival_slow_fraction=1.5,
        ).validate()


def test_off_mode_constructs_nothing():
    """The off-gate: arrival knobs set but async_mode='off' never builds
    the machinery — the round program is the exact pre-feature one."""
    cfg = ExperimentConfig(
        arrival_model="bimodal", round_deadline=1.0, async_buffer_size=2
    ).validate()
    assert cfg.async_mode == "off"
    assert AsyncFederation.from_config(cfg) is None


def test_refusals(tiny_config):
    """sign_SGD, Shapley, and the threaded oracle refuse with the flag
    named — same style as supports_lr_schedule."""
    with pytest.raises(ValueError, match="async_mode"):
        _run(tiny_config, distributed_algorithm="sign_SGD",
             learning_rate=0.01, **_ASYNC_ON)
    from distributed_learning_simulator_tpu.algorithms.shapley import (
        GTGShapley,
        MultiRoundShapley,
    )

    for cls in (MultiRoundShapley, GTGShapley):
        with pytest.raises(ValueError, match="async_mode"):
            cls(dataclasses.replace(tiny_config, **_ASYNC_ON))
    with pytest.raises(ValueError, match="async_mode"):
        _run(tiny_config, execution_mode="threaded", **_ASYNC_ON)


# ------------------------------------------- hand-computed staleness math


def test_staleness_discount_hand_computed():
    """classify() against hand math: latency 0.5 is on time (s=0),
    1.7 is one round late ((1+1)^-0.5), 3.2 is three rounds late
    ((1+3)^-0.5); a forced straggler is late at s >= 1 even when its
    drawn latency beat the deadline."""
    af = AsyncFederation(
        arrival_model="bimodal", slow_fraction=0.2, slow_factor=8.0,
        sigma=0.5, seed=0, deadline=1.0, buffer_size=2, alpha=0.5,
    )
    lat = jnp.asarray([0.5, 1.7, 3.2])
    on_time, s, disc, eff = af.classify(lat)
    assert on_time.tolist() == [True, False, False]
    assert s.tolist() == [0.0, 1.0, 3.0]
    assert eff.tolist() == lat.tolist()  # nothing forced: drawn latencies
    np.testing.assert_allclose(
        np.asarray(disc), [1.0, 2.0 ** -0.5, 4.0 ** -0.5], rtol=1e-6
    )
    forced = jnp.asarray([True, False, False])
    on_time_f, s_f, disc_f, eff_f = af.classify(lat, forced)
    assert on_time_f.tolist() == [False, False, False]
    assert s_f.tolist() == [1.0, 1.0, 3.0]
    np.testing.assert_allclose(float(disc_f[0]), 2.0 ** -0.5, rtol=1e-6)
    # The routed straggler's upload is delayed one full deadline, so the
    # simulated clock pays for it: the sync counterfactual now waits 1.5
    # (vs its 0.5 drawn arrival), not the on-time latency.
    np.testing.assert_allclose(np.asarray(eff_f), [1.5, 1.7, 3.2], rtol=1e-6)
    # deadline=inf: nobody is naturally late, staleness 0 across the board;
    # forced clients keep their drawn latency (finite telemetry).
    af_inf = dataclasses.replace(af, deadline=float("inf"))
    on_inf, s_inf, _, eff_inf = af_inf.classify(lat)
    assert on_inf.all() and not s_inf.any()
    _, s_inf_f, _, eff_inf_f = af_inf.classify(lat, forced)
    assert s_inf_f.tolist() == [1.0, 0.0, 0.0]
    assert eff_inf_f.tolist() == lat.tolist()
    np.testing.assert_allclose(
        float(staleness_discount(jnp.float32(3.0), 1.0)), 0.25, rtol=1e-6
    )


def test_buffer_trace_hand_computed_3_clients():
    """absorb_and_apply against a hand-computed 3-client scalar trace.

    Client A (size 3) beats the deadline with params 12; B (size 2,
    one round late, discount 1/2) uploads 16; C (size 1, three rounds
    late, discount 1/4) uploads 6. Global is 10, so the discounted late
    sum is 1.0*16 + 0.25*6 = 17.5 at weight 1.25 — a buffered delta of
    17.5 - 1.25*10 = 5.0. With K=2 the trigger fires immediately:
    beta = 1.25/(3 + 1.25) = 5/17 and the mix is
    10 + (12/17)*(12-10) + (5/17)*(5/1.25) = 10 + 44/17.
    """
    g = {"w": jnp.float32(10.0)}
    fresh = {"w": jnp.float32(12.0)}
    late_sum = {"w": jnp.float32(17.5)}
    a_tot = jnp.float32(3.0)
    b_tot = jnp.float32(1.25)
    n_late = jnp.int32(2)

    def make(K):
        return AsyncFederation(
            arrival_model="bimodal", slow_fraction=0.2, slow_factor=8.0,
            sigma=0.5, seed=0, deadline=1.0, buffer_size=K, alpha=1.0,
        )

    # K=2: insert + trigger in one round.
    af = make(2)
    state = af.init_state(g)
    new_g, applied, ins, nxt = af.absorb_and_apply(
        state, g, fresh, a_tot, late_sum, b_tot, n_late, jnp.float32(1.0)
    )
    assert bool(applied)
    np.testing.assert_allclose(
        float(new_g["w"]), 10.0 + 44.0 / 17.0, rtol=1e-6
    )
    # Inserted-but-not-reset state (what a rejected round keeps) holds
    # the hand-computed buffer; the normal next state reset it.
    np.testing.assert_allclose(float(ins["buf_sum"]["w"]), 5.0, rtol=1e-6)
    np.testing.assert_allclose(float(ins["buf_weight"]), 1.25, rtol=1e-6)
    assert int(ins["buf_count"]) == 2
    assert float(nxt["buf_sum"]["w"]) == 0.0
    assert float(nxt["buf_weight"]) == 0.0 and int(nxt["buf_count"]) == 0
    assert float(nxt["clock"]) == 1.0

    # K=3: same insert, no trigger — the fresh aggregate passes through
    # BIT-exactly and the buffer carries.
    af3 = make(3)
    new_g, applied, ins, nxt = af3.absorb_and_apply(
        af3.init_state(g), g, fresh, a_tot, late_sum, b_tot, n_late,
        jnp.float32(1.0),
    )
    assert not bool(applied)
    assert float(new_g["w"]) == 12.0
    np.testing.assert_allclose(float(nxt["buf_sum"]["w"]), 5.0, rtol=1e-6)
    assert int(nxt["buf_count"]) == 2

    # Second round on the carried buffer: one more late upload (size 2,
    # discount 1/2, params 20 vs global 12) tips the count to 3: buffer
    # becomes 5 + (20 - 12) = 13 at weight 2.25; beta = 2.25/(3 + 2.25).
    fresh2 = {"w": jnp.float32(14.0)}
    new_g2, applied2, _, nxt2 = af3.absorb_and_apply(
        nxt, {"w": jnp.float32(12.0)}, fresh2, a_tot,
        {"w": jnp.float32(1.0 * 20.0)}, jnp.float32(1.0), jnp.int32(1),
        jnp.float32(1.0),
    )
    assert bool(applied2)
    beta = 2.25 / 5.25
    expect = 12.0 + (1 - beta) * 2.0 + beta * (13.0 / 2.25)
    np.testing.assert_allclose(float(new_g2["w"]), expect, rtol=1e-6)
    assert int(nxt2["buf_count"]) == 0 and float(nxt2["clock"]) == 2.0

    # Non-finite late batch: dropped whole at insertion, buffer intact.
    new_g3, applied3, _, nxt3 = af3.absorb_and_apply(
        af3.init_state(g), g, fresh, a_tot, {"w": jnp.float32(float("nan"))},
        b_tot, n_late, jnp.float32(1.0),
    )
    assert not bool(applied3)
    assert float(new_g3["w"]) == 12.0
    assert float(nxt3["buf_sum"]["w"]) == 0.0 and int(nxt3["buf_count"]) == 0


# ------------------------------------------------ degenerate equivalence


def test_deadline_inf_bit_identical_to_sync(tiny_config):
    """The COMPILED async program at round_deadline=inf reproduces sync
    FedAvg bit-for-bit — participation sampling, dropout failure draws,
    quorum verdicts, and cohort hashes included — and its records say
    nothing was ever late or buffered."""
    cfg = dataclasses.replace(
        tiny_config, worker_number=8, round=3,
        participation_fraction=0.5, failure_mode="dropout",
        failure_prob=0.3, min_survivors=1,
    )
    keys = ("test_accuracy", "test_loss", "mean_client_loss",
            "survivor_count", "round_rejected", "cohort_hash")
    sync = _run(cfg)
    base = _series(sync, *keys)
    assert None not in base["cohort_hash"]  # sampling actually exercised
    a = _run(cfg, **{**_ASYNC_ON, "round_deadline": float("inf"),
                     "async_buffer_size": 4})
    assert _series(a, *keys) == base
    for h in a["history"]:
        rec = h["async"]
        assert rec["late"] == 0 and rec["buffer"] == 0
        assert not rec["applied"] and rec["mean_staleness"] is None
        # Closing at max latency == the sync counterfactual: no simulated
        # speedup to claim.
        assert rec["sim_round_s"] == rec["sim_round_sync_s"]
    assert a["async_speedup_ratio"] == 1.0
    assert sync["async_speedup_ratio"] is None  # off-mode result key


# ------------------------------------------------- deadline + buffer runs


def test_all_slow_cohort_buffers_then_applies(tiny_config, tmp_path):
    """arrival_slow_fraction=1 at deadline 1.0 makes EVERY upload late
    (slow factor 8, jitter >= 0.5 -> latency >= 4): rounds buffer 4
    uploads each; with K=6 the trigger first fires in round 1. The
    model must not move before the first apply, records must carry the
    v4 async sub-object (schema-validated), and report_run must render
    the staleness section."""
    import importlib.util

    import jsonschema

    cfg = dataclasses.replace(
        tiny_config, round=3, log_root=str(tmp_path / "log"),
        **{**_ASYNC_ON, "arrival_slow_fraction": 1.0,
           "round_deadline": 1.0, "async_buffer_size": 6},
    )
    result = run_simulation(cfg)
    recs = [h["async"] for h in result["history"]]
    assert [r["on_time"] for r in recs] == [0, 0, 0]
    assert [r["late"] for r in recs] == [4, 4, 4]
    assert [r["applied"] for r in recs] == [False, True, False]
    assert [r["buffer"] for r in recs] == [4, 0, 4]
    assert all(r["mean_staleness"] >= 3.0 for r in recs)
    # Deadline rounds close at 1.0 simulated second; sync would wait for
    # the slowest (>= 4.0) — the measured simulated-throughput win.
    assert all(r["sim_round_s"] == 1.0 for r in recs)
    assert result["async_speedup_ratio"] > 3.0
    assert result["sim_clock_seconds"] == pytest.approx(3.0)
    assert result["mean_buffer_occupancy"] == pytest.approx(8.0 / 3.0)
    # Model frozen until the buffer first applies (round 0 has no fresh
    # uploads and no trigger), then moves.
    accs = [h["test_accuracy"] for h in result["history"]]
    losses = [h["test_loss"] for h in result["history"]]
    assert losses[1] != losses[0] or accs[1] != accs[0]

    paths = glob.glob(os.path.join(cfg.log_root, "**", "metrics.jsonl"),
                      recursive=True)
    with open(paths[0]) as f:
        records = [json.loads(line) for line in f]
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "metrics_record.schema.json")) as f:
        schema = json.load(f)
    for r in records:
        assert r["schema_version"] == 4
        jsonschema.validate(r, schema)

    spec = importlib.util.spec_from_file_location(
        "report_run",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "report_run.py"),
    )
    report_run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report_run)
    summary = report_run.summarize_run(records)
    asy = summary["async_federation"]
    assert asy["rounds_reported"] == 3 and asy["applied_rounds"] == 1
    assert asy["late_total"] == 12 and asy["speedup_vs_sync"] > 3.0
    assert asy["staleness_histogram"]  # non-empty integer buckets
    rendered = "\n".join(report_run.render_summary(summary))
    assert "async federation" in rendered
    assert "staleness histogram" in rendered
    assert "speedup" in rendered


def test_straggler_fault_routes_into_buffer(tiny_config):
    """Satellite contract (robustness/faults.py): with the arrival model
    on, straggler-failed clients arrive AFTER the deadline — buffered,
    applied later, counted as survivors — instead of being discarded.
    The sync straggler run at failure_prob=1 never moves the model; the
    async run does once the buffer fires, and no round is rejected even
    with min_survivors at the full cohort."""
    cfg = dataclasses.replace(
        tiny_config, round=3, failure_mode="straggler", failure_prob=1.0,
        min_survivors=4,
    )
    sync = _run(cfg)
    assert len({h["test_loss"] for h in sync["history"]}) == 1  # frozen
    a = _run(cfg, **{**_ASYNC_ON, "round_deadline": float("inf"),
                     "async_buffer_size": 5})
    recs = [h["async"] for h in a["history"]]
    # Forced-late stragglers: staleness floored at 1 even at deadline=inf.
    assert [r["late"] for r in recs] == [4, 4, 4]
    assert all(r["mean_staleness"] == 1.0 for r in recs)
    assert [r["applied"] for r in recs] == [False, True, False]
    assert [h["survivor_count"] for h in a["history"]] == [4, 4, 4]
    assert not any(h["round_rejected"] for h in a["history"])
    assert len({h["test_loss"] for h in a["history"]}) > 1  # model moved


# ------------------------------------------------- composition contracts


def test_checkpoint_resume_replays_buffer(tiny_config, tmp_path):
    """The buffer carry is checkpointed: an interrupted async run
    resumes bit-identically to the uninterrupted one (buffer occupancy
    and apply rounds included), and async on/off mismatches between
    config and checkpoint are refused with the cause."""
    cfg = dataclasses.replace(
        tiny_config, round=4,
        **{**_ASYNC_ON, "arrival_slow_fraction": 1.0,
           "round_deadline": 1.0, "async_buffer_size": 6},
    )
    golden = _series(_run(cfg), "test_accuracy", "async")

    ckpt = str(tmp_path / "ckpt")
    first = _run(cfg, round=2, checkpoint_dir=ckpt, checkpoint_every=2)
    resumed = _run(cfg, checkpoint_dir=ckpt, checkpoint_every=2,
                   resume=True)
    stitched = {
        k: [h.get(k) for h in first["history"]]
        + [h.get(k) for h in resumed["history"]]
        for k in ("test_accuracy", "async")
    }
    assert stitched == golden

    with pytest.raises(ValueError, match="async_mode"):
        _run(tiny_config, checkpoint_dir=ckpt, resume=True)
    sync_ckpt = str(tmp_path / "sync_ckpt")
    _run(tiny_config, round=2, checkpoint_dir=sync_ckpt, checkpoint_every=2)
    with pytest.raises(ValueError, match="staleness-buffer"):
        _run(cfg, checkpoint_dir=sync_ckpt, resume=True)
