"""Checkpoint/resume: interrupted run == uninterrupted run (exceeds reference,
which has no persistence at all — SURVEY §5)."""

import dataclasses
import os
import pickle

import pytest

from distributed_learning_simulator_tpu.simulator import run_simulation
from distributed_learning_simulator_tpu.utils.checkpoint import (
    CheckpointCorruptError,
    gc_checkpoints,
    latest_checkpoint,
    load_checkpoint,
    load_latest_valid_checkpoint,
    save_checkpoint,
)


def test_save_load_roundtrip(tmp_path):
    import jax.numpy as jnp

    params = {"w": jnp.arange(4.0)}
    state = {"m": jnp.zeros(4)}
    path = save_checkpoint(str(tmp_path / "round_3.ckpt"), 3, params, state,
                           {"shapley_values": {0: {0: 1.0}}})
    ckpt = load_checkpoint(path)
    assert ckpt["round_idx"] == 3
    assert list(ckpt["global_params"]["w"]) == [0.0, 1.0, 2.0, 3.0]
    assert ckpt["algo_state"]["shapley_values"] == {0: {0: 1.0}}


def test_latest_checkpoint_ordering(tmp_path):
    import jax.numpy as jnp

    for r in (0, 2, 10):
        save_checkpoint(str(tmp_path / f"round_{r}.ckpt"), r,
                        {"w": jnp.zeros(1)}, {})
    assert latest_checkpoint(str(tmp_path)).endswith("round_10.ckpt")
    assert latest_checkpoint(str(tmp_path / "missing")) is None


def test_latest_checkpoint_skips_stray_files_and_resume_sweeps_tmps(tmp_path):
    """A stray `foo.ckpt` (no _N suffix) must be ignored, not crash the
    sort; stale `*.ckpt.tmp` left by a crashed writer are swept by the
    RESUME entry point only (read-only discovery must not race a live
    writer's tmp file)."""
    import jax.numpy as jnp

    save_checkpoint(str(tmp_path / "round_3.ckpt"), 3, {"w": jnp.zeros(1)}, {})
    (tmp_path / "foo.ckpt").write_bytes(b"not a checkpoint")
    (tmp_path / "round_9.ckpt.tmp").write_bytes(b"torn write")
    assert latest_checkpoint(str(tmp_path)).endswith("round_3.ckpt")
    assert (tmp_path / "round_9.ckpt.tmp").exists()  # discovery: no sweep
    found, _ = load_latest_valid_checkpoint(str(tmp_path))
    assert found.endswith("round_3.ckpt")
    assert not (tmp_path / "round_9.ckpt.tmp").exists()  # resume: swept
    assert (tmp_path / "foo.ckpt").exists()  # ignored, never deleted


def test_truncated_checkpoint_detected_and_fallback(tmp_path):
    """Acceptance: a checkpoint truncated to half its bytes fails the CRC
    at load, and discovery falls back to the previous valid one."""
    import jax.numpy as jnp

    for r in (0, 1):
        save_checkpoint(str(tmp_path / f"round_{r}.ckpt"), r,
                        {"w": jnp.full((8,), float(r))}, {})
    path1 = tmp_path / "round_1.ckpt"
    blob = path1.read_bytes()
    path1.write_bytes(blob[: len(blob) // 2])
    with pytest.raises(CheckpointCorruptError):
        load_checkpoint(str(path1))
    found, payload = load_latest_valid_checkpoint(str(tmp_path))
    assert found.endswith("round_0.ckpt")
    assert payload["round_idx"] == 0
    assert load_latest_valid_checkpoint(str(tmp_path / "none")) == (None, None)


def test_legacy_headerless_checkpoint_loads(tmp_path):
    """Pre-CRC checkpoints (raw pickle, no magic) still load."""
    legacy = {"round_idx": 7, "global_params": {"w": [1.0]},
              "client_state": None, "algo_state": {}, "rng_key": None}
    path = tmp_path / "round_7.ckpt"
    with open(path, "wb") as f:
        pickle.dump(legacy, f)
    assert load_checkpoint(str(path))["round_idx"] == 7
    # ...and a truncated legacy file surfaces as corrupt, not a raw
    # pickle exception, so the fallback scan keeps walking.
    path.write_bytes(path.read_bytes()[:10])
    with pytest.raises(CheckpointCorruptError):
        load_checkpoint(str(path))


def test_gc_checkpoints_keep_last(tmp_path):
    import jax.numpy as jnp

    for r in range(5):
        save_checkpoint(str(tmp_path / f"round_{r}.ckpt"), r,
                        {"w": jnp.zeros(1)}, {})
    removed = gc_checkpoints(str(tmp_path), keep_last=2)
    assert len(removed) == 3
    left = sorted(f for f in os.listdir(tmp_path) if f.endswith(".ckpt"))
    assert left == ["round_3.ckpt", "round_4.ckpt"]
    assert gc_checkpoints(str(tmp_path), keep_last=None) == []


def test_resume_falls_back_past_truncated_checkpoint(tiny_config, tmp_path):
    """Resume-level acceptance: truncating the latest checkpoint degrades
    resume by one interval (with a warning) instead of crashing, and the
    resumed history still matches the straight run bit-for-bit."""
    straight = run_simulation(
        dataclasses.replace(tiny_config, round=4), setup_logging=False
    )
    ckdir = tmp_path / "ck"
    run_simulation(
        dataclasses.replace(tiny_config, round=2, checkpoint_dir=str(ckdir),
                            checkpoint_every=1),
        setup_logging=False,
    )
    blob = (ckdir / "round_1.ckpt").read_bytes()
    (ckdir / "round_1.ckpt").write_bytes(blob[: len(blob) // 2])
    resumed = run_simulation(
        dataclasses.replace(tiny_config, round=4, checkpoint_dir=str(ckdir),
                            resume=True),
        setup_logging=False,
    )
    # fell back to round_0.ckpt -> resumed history covers rounds 1..3
    assert [h["round"] for h in resumed["history"]] == [1, 2, 3]
    straight_accs = [h["test_accuracy"] for h in straight["history"]]
    resumed_accs = [h["test_accuracy"] for h in resumed["history"]]
    assert resumed_accs == straight_accs[1:]


def test_checkpoint_keep_last_retention_end_to_end(tiny_config, tmp_path):
    ckdir = tmp_path / "ck"
    run_simulation(
        dataclasses.replace(tiny_config, round=4, checkpoint_dir=str(ckdir),
                            checkpoint_every=1, checkpoint_keep_last=2),
        setup_logging=False,
    )
    left = sorted(f for f in os.listdir(ckdir) if f.endswith(".ckpt"))
    assert left == ["round_2.ckpt", "round_3.ckpt"]
    resumed = run_simulation(
        dataclasses.replace(tiny_config, round=6, checkpoint_dir=str(ckdir),
                            checkpoint_every=1, checkpoint_keep_last=2,
                            resume=True),
        setup_logging=False,
    )
    assert [h["round"] for h in resumed["history"]] == [4, 5]


def test_a_save_is_not_the_next_rounds_time(tiny_config, tmp_path,
                                            monkeypatch):
    """Where the loop is not pipelined the device idles while a periodic
    checkpoint is written: the next round's ``round_seconds`` starts when
    the save ends (a pipelined loop writes while the next round runs, and
    its clock is left alone)."""
    import time

    from distributed_learning_simulator_tpu import simulator

    def slow_save(*args, **kwargs):
        time.sleep(0.5)
        return save_checkpoint(*args, **kwargs)

    monkeypatch.setattr(simulator, "save_checkpoint", slow_save)
    seconds = {}
    for pipelined in (False, True):
        result = run_simulation(
            dataclasses.replace(
                tiny_config, round=4, pipeline_rounds=pipelined,
                checkpoint_dir=str(tmp_path / f"ck_{pipelined}"),
                checkpoint_every=2),
            setup_logging=False,
        )
        seconds[pipelined] = [h["round_seconds"] for h in result["history"]]
    # Round 1 saves; round 2 is the round after.
    assert seconds[False][2] < 0.4
    assert max(seconds[True][2:]) > 0.45


def test_server_opt_resume_matches_straight_run(tiny_config, tmp_path):
    """FedAvgM momentum state survives checkpoint/resume bit-exactly."""
    fedavgm = dict(server_optimizer_name="sgd", server_learning_rate=1.0,
                   server_momentum=0.9)
    straight = run_simulation(
        dataclasses.replace(tiny_config, round=4, **fedavgm),
        setup_logging=False,
    )
    ckdir = str(tmp_path / "ck")
    run_simulation(
        dataclasses.replace(tiny_config, round=2, checkpoint_dir=ckdir,
                            checkpoint_every=1, **fedavgm),
        setup_logging=False,
    )
    resumed = run_simulation(
        dataclasses.replace(tiny_config, round=4, checkpoint_dir=ckdir,
                            resume=True, **fedavgm),
        setup_logging=False,
    )
    straight_accs = [h["test_accuracy"] for h in straight["history"]]
    resumed_accs = [h["test_accuracy"] for h in resumed["history"]]
    assert resumed_accs == straight_accs[2:]


def test_server_opt_resume_config_mismatch_raises(tiny_config, tmp_path):
    """Resuming an sgd-momentum checkpoint under adam must fail clearly, not
    crash inside the jitted update with a tree-structure error."""
    import pytest

    ckdir = str(tmp_path / "ck")
    run_simulation(
        dataclasses.replace(tiny_config, round=1, checkpoint_dir=ckdir,
                            checkpoint_every=1, server_optimizer_name="sgd",
                            server_momentum=0.9),
        setup_logging=False,
    )
    with pytest.raises(ValueError, match="server optimizer state"):
        run_simulation(
            dataclasses.replace(tiny_config, round=2, checkpoint_dir=ckdir,
                                resume=True, server_optimizer_name="adam"),
            setup_logging=False,
        )


def test_resume_matches_straight_run(tiny_config, tmp_path):
    """Run 4 rounds straight vs 2 + checkpoint + resume 2."""
    straight = run_simulation(
        dataclasses.replace(tiny_config, round=4), setup_logging=False
    )
    ckdir = str(tmp_path / "ck")
    run_simulation(
        dataclasses.replace(tiny_config, round=2, checkpoint_dir=ckdir,
                            checkpoint_every=1),
        setup_logging=False,
    )
    resumed = run_simulation(
        dataclasses.replace(tiny_config, round=4, checkpoint_dir=ckdir,
                            resume=True),
        setup_logging=False,
    )
    # resumed history covers rounds 2..3; accuracies must match the straight
    # run's same rounds exactly (same rng key chain).
    straight_accs = [h["test_accuracy"] for h in straight["history"]]
    resumed_accs = [h["test_accuracy"] for h in resumed["history"]]
    assert resumed_accs == straight_accs[2:]


def test_resume_unfolded_checkpoint_via_model_args(tiny_config, tmp_path):
    """The ADVICE-r3 escape hatch end-to-end: a checkpoint written with
    fold_stage1=False (pre-fold parameter structure) resumes ONLY with the
    matching model_args; the default (folded) config rejects it with the
    structure-mismatch error instead of failing inside jit."""
    base = dataclasses.replace(
        tiny_config, model_name="resnet18", worker_number=2, batch_size=8,
        n_train=64, n_test=32,
        dataset_args={"difficulty": 0.5, "shape": (32, 32, 3)},
        model_args={"fold_stage1": False},
    )
    ckdir = str(tmp_path / "ck")
    run_simulation(
        dataclasses.replace(base, round=1, checkpoint_dir=ckdir,
                            checkpoint_every=1),
        setup_logging=False,
    )
    # default (folded) structure must refuse the unfolded checkpoint
    with pytest.raises(ValueError, match="parameter structure"):
        run_simulation(
            dataclasses.replace(base, round=2, checkpoint_dir=ckdir,
                                resume=True, model_args={}),
            setup_logging=False,
        )
    # the matching model_args resume works
    resumed = run_simulation(
        dataclasses.replace(base, round=2, checkpoint_dir=ckdir,
                            resume=True),
        setup_logging=False,
    )
    assert len(resumed["history"]) == 1


def test_resume_client_state_mismatch_raises(tiny_config, tmp_path):
    """A checkpoint whose per-client state shape disagrees with the current
    config (e.g. sign_SGD momentum=0 -> no buffers, momentum>0 -> buffers)
    must fail loudly instead of crashing inside jit or silently dropping
    the saved buffers."""
    ckdir = str(tmp_path / "ck")
    run_simulation(
        dataclasses.replace(
            tiny_config, distributed_algorithm="sign_SGD",
            learning_rate=0.01, momentum=0.0, round=2,
            checkpoint_dir=ckdir, checkpoint_every=1,
        ),
        setup_logging=False,
    )
    with pytest.raises(ValueError, match="client_state"):
        run_simulation(
            dataclasses.replace(
                tiny_config, distributed_algorithm="sign_SGD",
                learning_rate=0.01, momentum=0.9, round=3,
                checkpoint_dir=ckdir, resume=True,
            ),
            setup_logging=False,
        )
