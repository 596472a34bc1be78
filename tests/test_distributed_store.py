"""Distributed shard store: owner bounds, assembly plan, store slice
semantics, sharded-checkpoint manifest discipline (ISSUE 15).

Everything except the last test is jax-free index math pinned without a
backend (the data/residency.py discipline); the final test pins the
``draw_pos`` permutation contract on the REAL streamed round program —
an owner-permuted cohort with permuted per-position draws trains every
client identically to the draw-order program.
"""

import numpy as np
import pytest

from distributed_learning_simulator_tpu.data.residency import (
    DistributedShardStore,
    host_axis_bounds,
    owner_of,
    plan_owner_assembly,
)
from distributed_learning_simulator_tpu.utils.checkpoint import (
    load_latest_valid_sharded_checkpoint,
    manifest_rounds,
    save_shard_checkpoint,
    validate_manifest,
    write_manifest,
)


def _bounds(n, hosts):
    return host_axis_bounds(n, [1] * hosts)


def test_host_axis_bounds_even_and_proportional():
    assert _bounds(8, 2).tolist() == [0, 4, 8]
    assert _bounds(9, 2).tolist() == [0, 4, 9]
    # Device-proportional: a host with 3 of 4 devices owns 3/4 of rows.
    assert host_axis_bounds(8, [3, 1]).tolist() == [0, 6, 8]
    assert owner_of([0, 3, 4, 7], _bounds(8, 2)).tolist() == [0, 0, 1, 1]


def test_plan_single_host_is_identity():
    """num_hosts == 1: the assignment is the identity and nothing
    spills — the zero-cost contract the single-process stream leg's
    bench floor rests on."""
    idx = np.array([6, 1, 3, 2])
    p = plan_owner_assembly(idx, _bounds(8, 1), _bounds(4, 1))
    assert p.draw_pos.tolist() == [0, 1, 2, 3]
    assert p.spill_q.size == 0
    assert p.idx_perm.tolist() == idx.tolist()


def test_plan_owner_contiguous_blocks_and_spill():
    """Own members fill the owner's block in draw order; the ownership
    imbalance (and only it) spills to the other host's free rows."""
    idx = np.array([6, 1, 3, 2])  # owners: 1, 0, 0, 0 under [0,4,8)
    p = plan_owner_assembly(idx, _bounds(8, 2), _bounds(4, 2))
    # Host 0's block (rows 0-1): its first two members in draw order.
    assert p.idx_perm[:2].tolist() == [1, 3]
    # Host 1's block: its one member, then host 0's overflow member.
    assert sorted(p.idx_perm[2:].tolist()) == [2, 6]
    # Exactly one spill entry: client 2 (owner 0) placed in block 1.
    assert p.spill_q.size == 1
    assert p.spill_owner.tolist() == [0]
    assert p.spill_block.tolist() == [1]
    assert idx[p.spill_q[0]] == 2
    # draw_pos inverts row_of.
    assert p.draw_pos[p.row_of].tolist() == list(range(4))


def test_plan_is_permutation_and_deterministic():
    rng = np.random.default_rng(0)
    owner_bounds = _bounds(1000, 4)
    block_bounds = _bounds(64, 4)
    for _ in range(10):
        idx = rng.choice(1000, size=64, replace=False)
        p1 = plan_owner_assembly(idx, owner_bounds, block_bounds)
        p2 = plan_owner_assembly(idx, owner_bounds, block_bounds)
        assert np.array_equal(p1.draw_pos, p2.draw_pos)
        assert sorted(p1.draw_pos.tolist()) == list(range(64))
        # Every non-spill row is served by its block's owner.
        for h in range(4):
            lo, hi = block_bounds[h], block_bounds[h + 1]
            owners = owner_of(p1.idx_perm[lo:hi], owner_bounds)
            n_own = int((owners == h).sum())
            # Own members come first, contiguously.
            assert (owners[:n_own] == h).all()
        # Spill accounting balances.
        assert p1.send_counts().sum() == p1.recv_counts().sum()
        assert p1.send_counts().sum() == p1.spill_q.size


def test_plan_spill_is_imbalance_only():
    """Spill is exactly sum over hosts of max(0, members - capacity) —
    the per-round ownership imbalance, not the cohort."""
    rng = np.random.default_rng(3)
    owner_bounds = _bounds(100, 2)
    block_bounds = _bounds(16, 2)
    for _ in range(20):
        idx = rng.choice(100, size=16, replace=False)
        p = plan_owner_assembly(idx, owner_bounds, block_bounds)
        owners = owner_of(idx, owner_bounds)
        expect = sum(
            max(0, int((owners == h).sum()) - 8) for h in range(2)
        )
        assert p.spill_q.size == expect


def test_distributed_store_owns_slice_and_maps_global_ids():
    x = np.arange(8 * 3, dtype=np.float32).reshape(8, 3)
    y = np.arange(8, dtype=np.int32)[:, None]
    m = np.ones((8, 1), np.float32)
    sz = np.arange(8, dtype=np.float32)
    s = DistributedShardStore(x, y, m, sz, host_id=1,
                             owner_bounds=_bounds(8, 2))
    assert (s.lo, s.hi, s.n_owned, s.n_hosts) == (4, 8, 4, 2)
    gx, _, _, gsz = s.gather_data(np.array([5, 7]))
    assert np.array_equal(gx, x[[5, 7]])
    assert np.array_equal(gsz, sz[[5, 7]])
    # Whole-slice gather (the full-population upload path).
    fx, _, _, _ = s.gather_data(None)
    assert np.array_equal(fx, x[4:8])
    with pytest.raises(IndexError, match="owns clients"):
        s.gather_data(np.array([3]))


def test_distributed_store_state_scatter_by_global_id():
    x = np.zeros((6, 2), np.float32)
    state = {"mom": np.zeros((3, 2), np.float32)}  # host 1 owns [3, 6)
    s = DistributedShardStore(
        x, np.zeros((6, 1), np.int32), np.ones((6, 1), np.float32),
        np.ones(6, np.float32), state=state, host_id=1,
        owner_bounds=_bounds(6, 2),
    )
    s.scatter_state(np.array([4]), {"mom": np.full((1, 2), 7.0,
                                                   np.float32)})
    assert s.state["mom"][1, 0] == 7.0
    got = s.gather_state(np.array([4]))
    assert got["mom"][0, 1] == 7.0
    with pytest.raises(NotImplementedError, match="dynamic"):
        s.grow(x, x, x, x)
    with pytest.raises(NotImplementedError, match="valuation"):
        s.attach_valuation(np.zeros(6))


def test_sharded_checkpoint_roundtrip_and_fallback(tmp_path):
    d = str(tmp_path)
    for r in (0, 1):
        for h in (0, 1):
            save_shard_checkpoint(d, r, h, 2, {
                "global_params": {"w": np.full(3, float(r))},
                "client_state": None,
                "algo_state": {"prev_metrics": {"loss": float(r)}},
                "rng_key": None,
            })
        write_manifest(d, r, {"n_hosts": 2, "n_clients": 8,
                              "owner_bounds": [0, 4, 8]})
    assert [r for r, _ in manifest_rounds(d)] == [0, 1]
    manifest, payload = load_latest_valid_sharded_checkpoint(d, 0, 2)
    assert manifest["round"] == 1
    assert payload["round_idx"] == 1 and payload["host_id"] == 0
    assert payload["global_params"]["w"][0] == 1.0
    # A round whose manifest never landed is invisible: discovery falls
    # back to the newest COMMITTED round (a host died pre-barrier).
    save_shard_checkpoint(d, 2, 0, 2, {"global_params": None,
                                       "client_state": None,
                                       "algo_state": {}, "rng_key": None})
    manifest, _ = load_latest_valid_sharded_checkpoint(d, 0, 2)
    assert manifest["round"] == 1
    # A manifest whose shard file is missing is skipped with a warning.
    write_manifest(d, 2, {"n_hosts": 2, "n_clients": 8,
                          "owner_bounds": [0, 4, 8]})
    manifest, _ = load_latest_valid_sharded_checkpoint(d, 0, 2)
    assert manifest["round"] == 1  # host 1's round-2 shard never landed


def test_resume_under_changed_host_count_refuses_at_discovery(tmp_path):
    """A REAL topology change (resume with a different host count, no
    manifest tampering) must refuse at discovery, not silently restart:
    this host's shard path derives from the CURRENT topology, so
    without the loader-level check the of-2 shards would read as
    'missing' and every round would be skipped."""
    d = str(tmp_path)
    for h in (0, 1):
        save_shard_checkpoint(d, 0, h, 2, {
            "global_params": None, "client_state": None,
            "algo_state": {}, "rng_key": None,
        })
    write_manifest(d, 0, {"n_hosts": 2, "n_clients": 8,
                          "owner_bounds": [0, 4, 8]})
    with pytest.raises(RuntimeError, match="topology mismatch"):
        load_latest_valid_sharded_checkpoint(d, 0, 3)
    # The matching topology still loads.
    manifest, payload = load_latest_valid_sharded_checkpoint(d, 0, 2)
    assert manifest["round"] == 0 and payload["host_id"] == 0


def test_validate_manifest_refusals_name_the_cause():
    base = {"n_hosts": 2, "n_clients": 8, "owner_bounds": [0, 4, 8]}
    validate_manifest(dict(base), n_hosts=2, n_clients=8,
                      owner_bounds=[0, 4, 8])
    with pytest.raises(RuntimeError, match="topology mismatch"):
        validate_manifest(dict(base), n_hosts=3, n_clients=8)
    with pytest.raises(RuntimeError, match="population mismatch"):
        validate_manifest(dict(base), n_hosts=2, n_clients=16)
    with pytest.raises(RuntimeError, match="ownership mismatch"):
        validate_manifest(dict(base), n_hosts=2, n_clients=8,
                          owner_bounds=[0, 6, 8])


CONFIG_REFUSALS = {
    "GLOBAL device count": dict(mesh_devices=None),
    "hashed": dict(participation_sampler="exact"),
    "async": dict(async_mode="on", arrival_model="bimodal"),
    "client_stats": dict(client_stats="on"),
    "valuation vector": dict(client_stats="off", client_valuation="on"),
    "persistent per-client state": dict(
        participation_fraction=1.0, reset_client_optimizer=False),
    "re-partition the distributed": dict(
        population="dynamic", join_rate=1.0),
    "stochastic-quantization": dict(
        distributed_algorithm="fed_quant", client_eval=False),
}


@pytest.mark.parametrize("cause", sorted(CONFIG_REFUSALS))
def test_config_refusals_name_causes(cause):
    """Streamed x multihost composes; every remaining refusal names its
    blocking cause (the PR 2/6/7 discipline)."""
    from distributed_learning_simulator_tpu.config import ExperimentConfig

    def cfg(**kw):
        base = dict(
            dataset_name="synthetic", model_name="mlp", worker_number=8,
            multihost=True, client_residency="streamed", mesh_devices=2,
            participation_fraction=0.5, participation_sampler="hashed",
        )
        base.update(kw)
        return ExperimentConfig(**base).validate()

    cfg()  # the lifted composition validates
    with pytest.raises(ValueError, match=cause):
        cfg(**CONFIG_REFUSALS[cause])


def test_draw_pos_permutes_back_to_draw_order(tiny_dataset):
    """The round-program half of the owner-permutation contract: calling
    the streamed round fn with owner-permuted operands + ``draw_pos``
    yields BIT-identical per-client outputs to the draw-order call
    (training keys and fault draws follow the client), with the
    aggregate equal up to summation order — pinned here on one device
    so the 2-process harness only has to cover placement."""
    import jax
    import numpy as np

    from distributed_learning_simulator_tpu.config import ExperimentConfig
    from distributed_learning_simulator_tpu.data.partition import (
        iid_partition,
        pack_client_shards,
    )
    from distributed_learning_simulator_tpu.factory import get_algorithm
    from distributed_learning_simulator_tpu.models.registry import (
        get_model,
        init_params,
    )
    from distributed_learning_simulator_tpu.parallel.engine import (
        make_decoder,
        make_optimizer,
    )

    config = ExperimentConfig(
        dataset_name="synthetic", model_name="mlp",
        distributed_algorithm="fed", worker_number=8, round=1, epoch=1,
        learning_rate=0.1, batch_size=16, n_train=256, n_test=128,
        log_level="ERROR", client_residency="streamed",
        participation_fraction=0.5, participation_sampler="hashed",
        failure_mode="dropout", failure_prob=0.3,  # positional draws
    ).validate()
    ds = tiny_dataset
    data = pack_client_shards(
        ds.x_train, ds.y_train,
        iid_partition(len(ds.x_train), 8, seed=0), batch_size=16,
    )
    model = get_model("mlp", num_classes=ds.num_classes)
    params = init_params(model, ds.x_train[:1], seed=0)
    opt = make_optimizer("SGD", 0.1)
    algo = get_algorithm("fed", config)
    round_fn = algo.make_round_fn(
        model.apply, opt, 8,
        preprocess=make_decoder(data.sample_shape) if data.compact
        else None,
    )
    key = jax.random.key(7)
    idx = np.asarray(algo.cohort_indices(key, 8))
    perm = np.array([2, 0, 3, 1])[: idx.size]
    idx_perm = idx[perm]

    def call(order, draw_pos):
        import jax.numpy as jnp

        take = lambda a: jnp.asarray(np.take(a, order, axis=0))  # noqa
        kw = {} if draw_pos is None else {
            "draw_pos": jnp.asarray(draw_pos, jnp.int32)
        }
        return round_fn(
            params, None, take(data.x), take(data.y), take(data.mask),
            take(data.sizes), jnp.asarray(order, jnp.int32), key, **kw
        )

    g_ref, _, aux_ref = call(idx, None)
    g_perm, _, aux_perm = call(idx_perm, perm)
    # Per-client outputs are bit-identical per CLIENT.
    ref_loss = np.asarray(aux_ref["client_loss"])
    perm_loss = np.asarray(aux_perm["client_loss"])
    assert np.array_equal(perm_loss, ref_loss[perm])
    # Fault draws followed the client too (survivor counts agree).
    assert int(aux_ref["survivor_count"]) == int(
        aux_perm["survivor_count"]
    )
    # The aggregate differs only by summation order.
    for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                    jax.tree_util.tree_leaves(g_perm)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-6, atol=2e-7)
