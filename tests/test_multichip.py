"""Multi-chip sharding on the fake 8-device CPU mesh.

The sharded run must produce the SAME results as the single-device vmap run
— sharding the client axis is an execution detail, not a semantics change.
This is the test story the reference's dormant multi-process path never had
(reference servers/server.py:10-13, simulator.py:56).
"""

import dataclasses

import jax
import numpy as np

from distributed_learning_simulator_tpu.parallel.mesh import (
    make_mesh,
    shard_client_data,
)
from distributed_learning_simulator_tpu.simulator import run_simulation


def test_mesh_construction():
    mesh = make_mesh(8)
    assert mesh.devices.shape == (8,)
    assert mesh.axis_names == ("clients",)


def test_mesh_shortfall_raises():
    """Requesting more devices than visible is a plain ValueError: no
    other backend's devices are substituted."""
    import pytest

    with pytest.raises(ValueError, match="only 8 visible on platform 'cpu'"):
        make_mesh(len(jax.devices()) + 1)


def test_shard_client_data_placement():
    mesh = make_mesh(8)
    x = np.zeros((16, 4), np.float32)
    (sharded,) = shard_client_data((x,), mesh)
    assert len(sharded.sharding.device_set) == 8


def _accs(cfg, **overrides):
    cfg = dataclasses.replace(cfg, **overrides)
    res = run_simulation(cfg, setup_logging=False)
    return [h["test_accuracy"] for h in res["history"]]


def test_sharded_matches_unsharded_fedavg(tiny_config):
    base = _accs(tiny_config, worker_number=8, round=3)
    sharded = _accs(tiny_config, worker_number=8, round=3, mesh_devices=8)
    np.testing.assert_allclose(sharded, base, atol=1e-4)


def test_sharded_matches_unsharded_sign_sgd(tiny_config):
    base = _accs(tiny_config, worker_number=8, round=2,
                 distributed_algorithm="sign_SGD", learning_rate=0.01)
    sharded = _accs(tiny_config, worker_number=8, round=2,
                    distributed_algorithm="sign_SGD", learning_rate=0.01,
                    mesh_devices=8)
    np.testing.assert_allclose(sharded, base, atol=1e-4)


def test_chunked_sharded_composition_matches_baseline(tiny_config):
    """client_chunk_size < cohort composed WITH mesh sharding — the flagship
    large-model configuration (ResNet-18 at scale needs both at once) —
    must equal the unchunked, unsharded run."""
    base = _accs(tiny_config, worker_number=16, round=3)
    both = _accs(tiny_config, worker_number=16, round=3, mesh_devices=8,
                 client_chunk_size=4)
    np.testing.assert_allclose(both, base, atol=1e-4)


def test_chunked_sharded_remainder_matches_baseline(tiny_config):
    """Chunk size that does not divide the cohort (remainder path) composed
    with mesh sharding."""
    base = _accs(tiny_config, worker_number=16, round=2)
    both = _accs(tiny_config, worker_number=16, round=2, mesh_devices=8,
                 client_chunk_size=5)
    np.testing.assert_allclose(both, base, atol=1e-4)


def test_chunked_sharded_materializing_path(tiny_config):
    """The materializing path (robust aggregation keeps the full client
    stack) under chunking + sharding together."""
    base = _accs(tiny_config, worker_number=16, round=2,
                 aggregation="median")
    both = _accs(tiny_config, worker_number=16, round=2,
                 aggregation="median", mesh_devices=8, client_chunk_size=4)
    np.testing.assert_allclose(both, base, atol=1e-4)


def test_sharded_matches_unsharded_fed_quant(tiny_config):
    """fed_quant's per-client payload RNG (stochastic quantize keys split
    inside the round program) under sharding: jax.random values are
    placement-independent, so the sharded run must match the single-device
    run to reduction-order tolerance. client_eval off keeps the fused
    path — the composition the flagship uses at scale."""
    kw = dict(worker_number=8, round=3, distributed_algorithm="fed_quant",
              client_eval=False)
    base = _accs(tiny_config, **kw)
    sharded = _accs(tiny_config, mesh_devices=8, **kw)
    np.testing.assert_allclose(sharded, base, atol=1e-4)


def test_sharded_client_stack_multiround_shapley(tiny_config):
    """Exact-Shapley post_round consuming a SHARDED aux['client_params']
    stack through _SubsetEvaluator (subset weighted means = einsums over
    the sharded client axis): per-round SVs must match the unsharded run
    to fp-reduction tolerance."""
    kw = dict(worker_number=8, round=2,
              distributed_algorithm="multiround_shapley_value")
    base = run_simulation(
        dataclasses.replace(tiny_config, **kw), setup_logging=False
    )
    sharded = run_simulation(
        dataclasses.replace(tiny_config, mesh_devices=8, **kw),
        setup_logging=False,
    )
    for hb, hs in zip(base["history"], sharded["history"]):
        np.testing.assert_allclose(hs["test_accuracy"], hb["test_accuracy"],
                                   atol=1e-4)
        sv_b, sv_s = hb["shapley_values"], hs["shapley_values"]
        np.testing.assert_allclose(
            [sv_s[i] for i in sorted(sv_s)], [sv_b[i] for i in sorted(sv_b)],
            atol=1e-4,
        )


def test_sharded_client_stack_gtg(tiny_config):
    """GTG's data-dependent permutation walk driven by a sharded client
    stack (with shapley_eval_samples subsampling the utility evals): SVs
    finite, accuracy matches the unsharded run."""
    kw = dict(worker_number=8, round=2,
              distributed_algorithm="GTG_shapley_value",
              shapley_eval_samples=64)
    base = run_simulation(
        dataclasses.replace(tiny_config, **kw), setup_logging=False
    )
    sharded = run_simulation(
        dataclasses.replace(tiny_config, mesh_devices=8, **kw),
        setup_logging=False,
    )
    np.testing.assert_allclose(
        sharded["history"][-1]["test_accuracy"],
        base["history"][-1]["test_accuracy"], atol=1e-4,
    )
    sv = sharded["history"][0]["shapley_values"]
    assert all(np.isfinite(v) for v in sv.values())


def test_chunked_sharded_participation_sampling(tiny_config):
    """Client sampling (cohort < population) + chunking + sharding: the
    three execution knobs compose."""
    cfg = dataclasses.replace(
        tiny_config, worker_number=16, round=2, participation_fraction=0.5,
        client_chunk_size=4, mesh_devices=8,
    )
    res = run_simulation(cfg, setup_logging=False)
    assert len(res["history"]) == 2
    assert all(np.isfinite(h["test_accuracy"]) for h in res["history"])


def test_uneven_clients_rejected(tiny_config):
    import pytest

    cfg = dataclasses.replace(tiny_config, worker_number=6, mesh_devices=8)
    with pytest.raises(ValueError, match="multiple of"):
        run_simulation(cfg, setup_logging=False)


def test_graft_entry_dryrun_pins_cpu_itself():
    """The driver invokes the graft entry in a fresh interpreter with ONLY
    XLA_FLAGS set (no JAX_PLATFORMS, no conftest): dryrun_multichip must
    pin the CPU backend itself and find the eight virtual devices."""
    import os
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    repo = os.path.join(os.path.dirname(__file__), "..")
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__; __graft_entry__.dryrun_multichip(8); "
         "import jax; print('DRYRUN_OK', jax.default_backend())"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "DRYRUN_OK cpu" in proc.stdout


def test_graft_entry_dryrun():
    """The driver's multi-chip compile check must pass on 8 virtual devices."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "__graft_entry__",
        os.path.join(os.path.dirname(__file__), "..", "__graft_entry__.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    fn, args = mod.entry()
    out = jax.jit(fn)(*args)
    assert np.isfinite(np.asarray(out)).all()
    mod.dryrun_multichip(8)
