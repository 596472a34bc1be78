"""Streamed client-state residency (config.client_residency='streamed';
data/residency.py + parallel/streaming.py): the full-N per-client arrays
live in a host shard store and only the sampled cohort's slice is
uploaded per dispatch, double-buffered so the next dispatch's cohort
transfers while the current one computes. The contract under test: the
streamed history is BIT-identical to the resident one — cohort hashes,
failure draws, and training metrics included — across the FedAvg family,
sign_SGD, fed_quant, and checkpoint/resume, while
'resident' (the default) keeps the exact pre-feature program.

The HostShardStore unit tests are jax-free by design (the module imports
only numpy): the host gather/scatter index math mirrors the resident
program's ops/cohort.py device ops, and pinning it without a backend is
what keeps the two implementations semantically paired.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from distributed_learning_simulator_tpu.config import ExperimentConfig
from distributed_learning_simulator_tpu.data.residency import (
    HostShardStore,
    synthetic_stream_shards,
    tree_bytes,
    tree_map_np,
)
from distributed_learning_simulator_tpu.simulator import run_simulation


def _run(cfg, **overrides):
    cfg = dataclasses.replace(cfg, **overrides)
    return run_simulation(cfg, setup_logging=False)


def _series(result, *keys):
    return {k: [h.get(k) for h in result["history"]] for k in keys}


def _read_metrics(log_root):
    import glob

    paths = glob.glob(
        os.path.join(str(log_root), "**", "metrics.jsonl"), recursive=True
    )
    assert len(paths) == 1
    with open(paths[0]) as f:
        return [json.loads(line) for line in f]


_BIT_KEYS = ("test_accuracy", "test_loss", "mean_client_loss",
             "cohort_hash", "survivor_count", "round_rejected")


# ------------------------------------------------------------- validation


def test_config_validation():
    with pytest.raises(ValueError, match="client_residency"):
        ExperimentConfig(client_residency="paged").validate()
    with pytest.raises(ValueError, match="vmap execution mode"):
        ExperimentConfig(
            client_residency="streamed", execution_mode="threaded"
        ).validate()
    # Single-host mesh sharding COMPOSES with streamed residency (the
    # streamer uploads straight into the client-axis PartitionSpec
    # layout); multi-host still refuses naming the cause (the host
    # shard store is single-process).
    ExperimentConfig(client_residency="streamed", mesh_devices=2).validate()
    with pytest.raises(ValueError, match="multihost"):
        ExperimentConfig(
            client_residency="streamed", multihost=True
        ).validate()
    ExperimentConfig(client_residency="streamed").validate()


def test_default_is_resident():
    assert ExperimentConfig().client_residency == "resident"


def test_shapley_refuses_streamed(tiny_config):
    """The Shapley family's subset re-evaluation assumes a resident
    per-client stack; the simulator refuses before any dispatch, naming
    the flag."""
    with pytest.raises(ValueError, match="client_residency"):
        _run(tiny_config, distributed_algorithm="multiround_shapley_value",
             client_residency="streamed")


# ------------------------------------------ host shard store (jax-free)


def _store(n=6, shard=4, dim=3, state=False):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, shard, dim)).astype(np.float32)
    y = rng.integers(0, 10, size=(n, shard)).astype(np.int32)
    mask = np.ones((n, shard), dtype=np.float32)
    sizes = np.full(n, float(shard), dtype=np.float32)
    st = None
    if state:
        st = {"mom": rng.normal(size=(n, dim)).astype(np.float32),
              "count": np.zeros(n, dtype=np.int32)}
    return HostShardStore(x, y, mask, sizes, state=st)


def test_store_gather_matches_fancy_index():
    store = _store(state=True)
    idx = np.array([4, 1, 3])
    gx, gy, gm, gs = store.gather_data(idx)
    np.testing.assert_array_equal(gx, store.x[idx])
    np.testing.assert_array_equal(gy, store.y[idx])
    np.testing.assert_array_equal(gm, store.mask[idx])
    np.testing.assert_array_equal(gs, store.sizes[idx])
    gst = store.gather_state(idx)
    np.testing.assert_array_equal(gst["mom"], store.state["mom"][idx])


def test_store_gather_none_is_whole_population():
    store = _store()
    gx, gy, gm, gs = store.gather_data(None)
    assert gx is store.x and gs is store.sizes  # no copy
    assert store.gather_state(None) is None  # stateless store


def test_store_scatter_roundtrip_preserves_unselected_rows():
    store = _store(state=True)
    before = {k: v.copy() for k, v in store.state.items()}
    idx = np.array([0, 5, 2])
    update = {"mom": np.full((3, 3), 7.0, np.float32),
              "count": np.array([1, 2, 3], np.int32)}
    store.scatter_state(idx, update)
    np.testing.assert_array_equal(store.state["mom"][idx], update["mom"])
    np.testing.assert_array_equal(store.state["count"][idx], update["count"])
    untouched = np.setdiff1d(np.arange(6), idx)
    np.testing.assert_array_equal(
        store.state["mom"][untouched], before["mom"][untouched]
    )


def test_store_index_out_of_range_rejected():
    store = _store(state=True)
    with pytest.raises(IndexError, match="out of range"):
        store.gather_data(np.array([0, 6]))
    with pytest.raises(IndexError, match="out of range"):
        store.scatter_state(np.array([-1]), store.gather_state(np.array([0])))


def test_store_axis_mismatch_rejected():
    x = np.zeros((4, 2, 3), np.float32)
    with pytest.raises(ValueError, match="length mismatch"):
        HostShardStore(x, np.zeros((3, 2), np.int32),
                       np.ones((4, 2), np.float32), np.ones(4, np.float32))
    with pytest.raises(ValueError, match="client-axis length"):
        HostShardStore(x, np.zeros((4, 2), np.int32),
                       np.ones((4, 2), np.float32), np.ones(4, np.float32),
                       state={"mom": np.zeros((5, 3), np.float32)})


def test_tree_map_np_handles_namedtuples():
    import collections

    Opt = collections.namedtuple("Opt", ["mu", "nu"])
    tree = {"o": Opt(np.ones(2), np.zeros(2)), "none": None,
            "l": [np.full(2, 3.0)]}
    doubled = tree_map_np(lambda a: a * 2, tree)
    assert isinstance(doubled["o"], Opt)
    np.testing.assert_array_equal(doubled["o"].mu, np.full(2, 2.0))
    assert doubled["none"] is None
    np.testing.assert_array_equal(doubled["l"][0], np.full(2, 6.0))
    assert tree_bytes(tree) == 3 * 2 * 8  # three f64[2] leaves


def test_store_bytes_accounting_scales_by_cohort():
    store = _store(n=6, shard=4, dim=3)
    assert store.data_bytes() == (store.x.nbytes + store.y.nbytes
                                  + store.mask.nbytes + store.sizes.nbytes)
    assert store.cohort_data_bytes(2) * 3 == store.data_bytes()


def test_synthetic_stream_shards_layout():
    """The vectorized population generator must produce the packed
    ClientData layout (uint8-compact x, int32 y, full masks) at any N —
    pack_client_shards' Python loop is what it replaces at the million
    scale."""
    rng = np.random.default_rng(0)
    x_train = rng.uniform(size=(32, 2, 2, 1)).astype(np.float32)
    y_train = rng.integers(0, 10, size=32)
    cd = synthetic_stream_shards(x_train, y_train, n_clients=50,
                                 shard_size=8, seed=1)
    assert cd.x.shape == (50, 8, 4) and cd.x.dtype == np.uint8
    assert cd.y.shape == (50, 8) and cd.y.dtype == np.int32
    assert cd.mask.shape == (50, 8) and float(cd.mask.min()) == 1.0
    assert cd.sample_shape == (2, 2, 1)
    # Deterministic in the seed.
    cd2 = synthetic_stream_shards(x_train, y_train, 50, 8, seed=1)
    np.testing.assert_array_equal(cd.x, cd2.x)
    # Out-of-[0,1] pools keep float32 + sample shape, like
    # pack_client_shards' range fallback (uint8 would clip the data).
    gauss = rng.normal(size=(32, 2, 2, 1)).astype(np.float32)
    cd3 = synthetic_stream_shards(gauss, y_train, 10, 4, seed=1)
    assert cd3.x.dtype == np.float32 and cd3.x.shape == (10, 4, 2, 2, 1)


# -------------------------------------------------- budget model refusals


def test_residency_feasibility_names_the_flag(monkeypatch):
    """An over-budget resident run must refuse up front naming
    client_residency (not die as an opaque allocation failure); the
    streamed check sizes by the double-buffered cohort instead."""
    import distributed_learning_simulator_tpu.simulator as sim

    monkeypatch.setattr(sim, "_device_budget_bytes", lambda cfg: 1024.0)
    cfg = ExperimentConfig(worker_number=8, participation_fraction=0.25)
    params = {"w": np.zeros((4, 4), np.float32)}
    with pytest.raises(ValueError, match="client_residency='streamed'"):
        sim._assert_residency_feasible(cfg, params, 8, data_bytes=1 << 20)
    cfg_s = dataclasses.replace(cfg, client_residency="streamed")
    with pytest.raises(ValueError, match="cohort footprint"):
        sim._assert_residency_feasible(cfg_s, params, 8, data_bytes=1 << 20)
    # The streamed budget is 2 x cohort x per-client bytes — a population
    # far over budget passes once the cohort slice fits.
    monkeypatch.setattr(sim, "_device_budget_bytes", lambda cfg: 600_000.0)
    sim._assert_residency_feasible(cfg_s, params, 8, data_bytes=1 << 20)
    with pytest.raises(ValueError, match="client_residency='resident'"):
        sim._assert_residency_feasible(cfg, params, 8, data_bytes=1 << 20)
    # Full-cohort streamed (participation 1.0, e.g. sign_SGD): ONE
    # startup upload, no double buffer — 1x data must fit, not 2x.
    cfg_full = dataclasses.replace(cfg_s, participation_fraction=1.0)
    monkeypatch.setattr(
        sim, "_device_budget_bytes", lambda cfg: 1.5 * (1 << 20)
    )
    sim._assert_residency_feasible(cfg_full, params, 8, data_bytes=1 << 20)
    monkeypatch.setattr(sim, "_device_budget_bytes", lambda cfg: 900_000.0)
    with pytest.raises(ValueError, match="full-cohort"):
        sim._assert_residency_feasible(cfg_full, params, 8,
                                       data_bytes=1 << 20)


# ------------------------------------------------------------ bit identity


def test_streamed_matches_resident_fedavg_full_feature(tiny_config):
    """FedAvg with participation sampling, dropout faults, quorum, and a
    cosine schedule: the streamed history reproduces the resident one
    bit-for-bit — cohort hashes (the sampling draws) and failure draws
    included."""
    cfg = dataclasses.replace(
        tiny_config, worker_number=8, round=3, participation_fraction=0.5,
        failure_mode="dropout", failure_prob=0.3, min_survivors=1,
        lr_schedule="cosine",
    )
    base = _series(_run(cfg), *_BIT_KEYS, "lr_factor")
    streamed = _series(
        _run(cfg, client_residency="streamed"), *_BIT_KEYS, "lr_factor"
    )
    assert base == streamed
    assert None not in base["cohort_hash"]  # sampling actually exercised


def test_streamed_matches_resident_sign_sgd_momentum(tiny_config):
    """sign_SGD's per-step vote synchronizes the whole population — the
    full-cohort streamed regime (one startup upload, resident program
    shape) including persistent momentum buffers."""
    cfg = dataclasses.replace(
        tiny_config, distributed_algorithm="sign_SGD", learning_rate=0.01,
        momentum=0.9, round=3,
    )
    keys = ("test_accuracy", "test_loss", "mean_client_loss",
            "uplink_compression_ratio")
    assert _series(_run(cfg), *keys) == _series(
        _run(cfg, client_residency="streamed"), *keys
    )


def test_streamed_matches_resident_fed_quant(tiny_config):
    cfg = dataclasses.replace(
        tiny_config, distributed_algorithm="fed_quant", worker_number=8,
        round=3, participation_fraction=0.5,
    )
    keys = ("test_accuracy", "test_loss", "cohort_hash",
            "uplink_compression_ratio")
    assert _series(_run(cfg), *keys) == _series(
        _run(cfg, client_residency="streamed"), *keys
    )


def test_streamed_matches_resident_persistent_client_state(tiny_config):
    """reset_client_optimizer=False under sampling: the cohort's
    optimizer state gathers from the host store and scatters back each
    round — the writeback path — and must still match the resident
    in-program gather/scatter bit-for-bit."""
    cfg = dataclasses.replace(
        tiny_config, worker_number=8, round=4, participation_fraction=0.5,
        reset_client_optimizer=False,
    )
    base = _series(_run(cfg), *_BIT_KEYS)
    assert base == _series(_run(cfg, client_residency="streamed"),
                           *_BIT_KEYS)


def test_streamed_checkpoint_resume_mid_run(tiny_config, tmp_path):
    """Kill/resume mid-run with persistent per-client state: the host
    store is the checkpoint source of truth, and the stitched streamed
    history equals the uninterrupted RESIDENT run bit-for-bit."""
    cfg = dataclasses.replace(
        tiny_config, worker_number=8, round=5, participation_fraction=0.5,
        reset_client_optimizer=False,
    )
    golden = [h["test_accuracy"] for h in _run(cfg)["history"]]

    ckpt = str(tmp_path / "ckpt")
    first = _run(cfg, round=3, client_residency="streamed",
                 checkpoint_dir=ckpt, checkpoint_every=2)
    resumed = _run(cfg, client_residency="streamed", checkpoint_dir=ckpt,
                   checkpoint_every=2, resume=True)
    # Last checkpoint is round_1.ckpt: the resumed run replays round 2
    # (the chaos-resume replay discipline) then continues to 4.
    assert [h["round"] for h in resumed["history"]] == [2, 3, 4]
    stitched = [h["test_accuracy"] for h in first["history"][:2]] + [
        h["test_accuracy"] for h in resumed["history"]
    ]
    assert stitched == golden


# ------------------------------------------- mesh composition (ISSUE 10)
#
# Streamed residency composes with single-host mesh sharding: the
# streamer uploads each cohort slice directly into the client-axis
# PartitionSpec layout (per-shard host->device transfers addressed by
# the mesh's client-axis ownership; parallel/streaming.py). The pins:
# cohort draws (the round-key replay) are BIT-identical across every
# residency x mesh combination, and for a FIXED mesh the streamed run
# equals the resident run — streaming is a residency detail, never a
# semantics change. Mesh-vs-single-device metric equality is to
# reduction-order tolerance, the same contract the resident mesh tests
# (test_multichip.py) have always pinned: sharding the f32 client-axis
# reduction reorders the sum.


def _mesh_series(cfg, *keys, **overrides):
    res = _run(cfg, **overrides)
    return {k: [h.get(k) for h in res["history"]] for k in keys}


def test_streamed_mesh_matches_resident_mesh_fedavg(tiny_config):
    """FedAvg sampled cohort, same 4-device mesh: streamed (uploaded
    pre-gathered sharded slices) vs resident (in-program gather from
    the sharded population) — bit-equal cohort draws, metrics equal to
    reduction-order tolerance."""
    cfg = dataclasses.replace(
        tiny_config, worker_number=16, round=3, participation_fraction=0.5,
        mesh_devices=4,
    )
    resident = _mesh_series(cfg, *_BIT_KEYS)
    streamed = _mesh_series(cfg, *_BIT_KEYS, client_residency="streamed")
    assert streamed["cohort_hash"] == resident["cohort_hash"]
    assert None not in streamed["cohort_hash"]
    np.testing.assert_allclose(
        streamed["test_loss"], resident["test_loss"], atol=1e-4
    )
    np.testing.assert_allclose(
        streamed["test_accuracy"], resident["test_accuracy"], atol=1e-3
    )


def test_streamed_mesh_matches_streamed_single_device(tiny_config):
    """Same streamed program, mesh vs one device: cohort draws
    bit-equal (the host replay never touches the mesh), metrics to the
    mesh reduction-order tolerance — and the hashed O(cohort) sampler
    composes identically."""
    for sampler in ("exact", "hashed"):
        cfg = dataclasses.replace(
            tiny_config, worker_number=16, round=3,
            participation_fraction=0.5, client_residency="streamed",
            participation_sampler=sampler,
        )
        single = _mesh_series(cfg, *_BIT_KEYS)
        mesh = _mesh_series(cfg, *_BIT_KEYS, mesh_devices=4)
        assert mesh["cohort_hash"] == single["cohort_hash"], sampler
        np.testing.assert_allclose(
            mesh["test_loss"], single["test_loss"], atol=1e-4
        )


def test_streamed_mesh_sign_sgd_full_cohort(tiny_config):
    """sign_SGD (full-cohort streamed regime: one startup upload,
    population-shaped and mesh-sharded): bit-identical to the resident
    mesh run — the discrete per-step vote quantizes away reduction
    noise."""
    cfg = dataclasses.replace(
        tiny_config, distributed_algorithm="sign_SGD", learning_rate=0.01,
        momentum=0.9, worker_number=16, round=2, mesh_devices=4,
    )
    keys = ("test_accuracy", "test_loss", "mean_client_loss")
    assert _mesh_series(cfg, *keys) == _mesh_series(
        cfg, *keys, client_residency="streamed"
    )


def test_streamed_mesh_fed_quant(tiny_config):
    """fed_quant, same mesh: bit-equal cohorts; the stochastic
    quantizer DISCRETIZES reduction-order ulps into visible (but
    bounded) metric deltas, so the tolerance is looser than plain
    fed's."""
    cfg = dataclasses.replace(
        tiny_config, distributed_algorithm="fed_quant", worker_number=16,
        round=3, participation_fraction=0.5, mesh_devices=4,
        client_eval=False,
    )
    resident = _mesh_series(cfg, *_BIT_KEYS)
    streamed = _mesh_series(cfg, *_BIT_KEYS, client_residency="streamed")
    assert streamed["cohort_hash"] == resident["cohort_hash"]
    np.testing.assert_allclose(
        streamed["test_loss"], resident["test_loss"], atol=5e-3
    )


def test_streamed_mesh_persistent_state(tiny_config):
    """The remaining composition axis on one mesh: the persistent-state
    writeback path (sharded cohort state gathered from and scattered
    back to the host store)."""
    cfg = dataclasses.replace(
        tiny_config, worker_number=16, round=4, participation_fraction=0.5,
        mesh_devices=4, client_residency="streamed",
        reset_client_optimizer=False,
    )
    streamed = _mesh_series(cfg, *_BIT_KEYS)
    resident = _mesh_series(cfg, *_BIT_KEYS, client_residency="resident")
    assert streamed["cohort_hash"] == resident["cohort_hash"]
    np.testing.assert_allclose(
        streamed["test_loss"], resident["test_loss"], atol=1e-4,
    )


def test_streamed_mesh_cohort_divisibility_refused(tiny_config):
    """Unsupported combination still refuses naming the cause: the
    COHORT (not the population) is the device-resident client axis
    under streamed sampling, so it must divide the mesh."""
    cfg = dataclasses.replace(
        tiny_config, worker_number=16, round=2, participation_fraction=0.5,
        client_residency="streamed", mesh_devices=3,
    )
    with pytest.raises(ValueError, match="cohort size"):
        _run(cfg)


# ------------------------------------------------------ stream telemetry


def test_stream_records_and_result_fields(tiny_config, tmp_path):
    """Streamed runs emit the schema-v5 stream sub-object (validated
    against the checked-in JSON schema) and the result dict's transfer
    totals; resident runs stay pinned at the pre-feature layout with no
    stream fields."""
    jsonschema = pytest.importorskip("jsonschema")
    cfg = dataclasses.replace(
        tiny_config, worker_number=8, round=3, participation_fraction=0.5,
        # momentum gives the persistent client state real bytes (plain
        # sgd's optax state is empty — nothing to write back).
        reset_client_optimizer=False, momentum=0.9,
    )
    res = run_simulation(dataclasses.replace(
        cfg, client_residency="streamed", log_root=str(tmp_path / "s")
    ))
    assert res["client_residency"] == "streamed"
    assert 0.0 <= res["stream_overlap_ratio"] <= 1.0
    assert res["stream_h2d_bytes"] > 0
    assert res["stream_d2h_bytes"] > 0  # persistent state wrote back
    records = _read_metrics(tmp_path / "s")
    schema = json.load(open(
        os.path.join(os.path.dirname(__file__), "data",
                     "metrics_record.schema.json")
    ))
    assert len(records) == 3
    for rec in records:
        assert rec["schema_version"] == 5
        jsonschema.validate(rec, schema)
        assert rec["stream"]["h2d_bytes"] > 0

    resident = run_simulation(
        dataclasses.replace(cfg, log_root=str(tmp_path / "r"))
    )
    assert resident["stream_overlap_ratio"] is None
    for rec in _read_metrics(tmp_path / "r"):
        assert "stream" not in rec and "schema_version" not in rec


def test_sample_phase_and_stream_sampler_fields(tiny_config, tmp_path):
    """The cohort-draw replay cost is visible end to end: `sample` in
    the telemetry phase table (carved out of the client_step window it
    overlaps), sampler/sample_ms in the schema-v5 stream record, and
    the run total in the result dict — for both sampler modes."""
    jsonschema = pytest.importorskip("jsonschema")
    schema = json.load(open(
        os.path.join(os.path.dirname(__file__), "data",
                     "metrics_record.schema.json")
    ))
    for sampler in ("exact", "hashed"):
        root = tmp_path / sampler
        res = run_simulation(dataclasses.replace(
            tiny_config, worker_number=8, round=3,
            participation_fraction=0.5, client_residency="streamed",
            participation_sampler=sampler, telemetry_level="basic",
            log_root=str(root),
        ))
        assert res["participation_sampler"] == sampler
        assert res["stream_sample_seconds"] > 0
        records = _read_metrics(root)
        for rec in records:
            jsonschema.validate(rec, schema)
            assert rec["stream"]["sampler"] == sampler
            assert rec["stream"]["sample_ms"] >= 0
        # Every round with a prefetched next cohort records the draw in
        # its own `sample` phase (the final round draws nothing).
        phases = [rec["telemetry"]["phase_seconds"] for rec in records]
        assert all("sample" in p for p in phases[:-1])
        # Full-cohort streamed (no draw): no sampler fields, no phase.
        res_full = run_simulation(dataclasses.replace(
            tiny_config, worker_number=8, round=2,
            client_residency="streamed", participation_sampler=sampler,
            telemetry_level="basic", log_root=str(tmp_path / ("f" + sampler)),
        ))
        assert res_full["stream_sample_seconds"] == 0.0
        for rec in _read_metrics(tmp_path / ("f" + sampler)):
            assert "sampler" not in rec.get("stream", {})


def test_report_run_renders_transfer_row(tiny_config, tmp_path):
    """report_run.py over a streamed run's artifacts: the stream summary
    aggregates per-dispatch transfer stats and the terminal rendering
    carries the h2d transfer row."""
    import importlib.util

    cfg = dataclasses.replace(
        tiny_config, worker_number=8, round=3, participation_fraction=0.5,
        reset_client_optimizer=False, momentum=0.9,
        telemetry_level="basic", log_root=str(tmp_path / "art"),
        client_residency="streamed",
    )
    run_simulation(cfg)
    records = _read_metrics(tmp_path / "art")

    spec = importlib.util.spec_from_file_location(
        "report_run",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "report_run.py"),
    )
    report_run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report_run)
    summary = report_run.summarize_run(records)
    s = summary["stream"]
    assert s["uploads"] == 3 and s["h2d_bytes"] > 0 and s["d2h_bytes"] > 0
    assert 0.0 <= s["overlap_ratio"] <= 1.0
    text = "\n".join(report_run.render_summary(summary))
    assert "h2d_stream" in text and "streamed transfers: 3 upload(s)" in text
