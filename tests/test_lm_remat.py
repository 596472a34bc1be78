"""Which of a language model's blocks are wrapped in ``nn.remat``
(models/lm_parts.py ``leading_remat``, ``rematerialised_blocks``): the
last blocks keep their residuals where the device has room, the first
are recomputed, and either form computes the same loss and gradient."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_learning_simulator_tpu.models import lm_parts as parts
from distributed_learning_simulator_tpu.models.registry import get_model
from distributed_learning_simulator_tpu.parallel.engine import make_loss_fn
from test_olmo_hybrid import _PRESETS, _token_dataset, share_args, tree_close


def _no_room(monkeypatch):
    monkeypatch.setattr(parts, "device_memory_limit", lambda: (0, "none"))


@pytest.mark.parametrize("n,kept", [(4, 0), (4, 1), (4, 3), (4, 4), (5, 2),
                                    (1, 0), (1, 1)])
@pytest.mark.parametrize("size", [1, 3 * 2**30])
def test_the_room_keeps_the_last_blocks(n, kept, size):
    """A room of exactly ``kept`` blocks' bytes keeps the last ``kept``
    and rematerialises the ``n - kept`` before them; a byte less keeps
    one fewer; none, or less than one block, keeps none."""
    def remat(room):
        return parts.leading_remat(n, [size] * n, room)

    assert remat(kept * size) == n - kept
    if kept:
        assert remat(kept * size - 1) == n - kept + 1
    assert remat(0) == n
    assert remat(-size) == n


def test_a_block_is_never_recomputed_after_one_that_keeps():
    """Blocks of different sizes: the count walks back from the last and
    stops at the first that does not fit, though a smaller one before it
    would, and draws no block's bytes after that one (none where there
    is no room)."""
    sizes = [1, 5, 2, 3]

    def remat(room):
        drawn = []
        n = parts.leading_remat(len(sizes), (
            drawn.append(size) or size for size in reversed(sizes)), room)
        assert drawn == (sizes[::-1][:len(sizes) - n + 1] if room else [])
        return n

    assert [remat(room) for room in range(12)] == [
        4, 4, 4, 3, 3, 2, 2, 2, 2, 2, 1, 0]


_MODELS = {
    "afmoe": _PRESETS["afmoe"],
    "solar_open2": _PRESETS["solar_open2"],
    "olmo_hybrid": share_args(),
}


def _model(name, dtype="float32"):
    args = {k: v for k, v in _MODELS[name].items() if k != "dtype"}
    return get_model(name, num_classes=96, **args, dtype=dtype)


#: Olmo-Hybrid's gradients move with XLA's order of f32 operations: the
#: delta rule's triangular inverse carries a rounding difference far. The
#: same kept program run op by op and compiled differs by 2.7e-5 of a
#: tensor's largest entry, the recomputed form by 9.5e-5; the model's own
#: tests hold its gradients to the reference at 2e-4 of that scale.
_SCALED = {"olmo_hybrid": 2e-4}


@pytest.mark.parametrize("name", sorted(_MODELS))
def test_the_kept_form_is_the_rematerialised_forms_loss_and_gradient(
        name, monkeypatch):
    """At the tiny presets the CPU's assumed 16 GiB keeps every block;
    with no room every block is recomputed. Both give the same loss and
    gradient in f32: to 1e-6, or to a share of each tensor's largest
    entry where f32's order of operations moves the gradient more
    (``_SCALED``)."""
    model = _model(name)
    tokens = jax.random.randint(jax.random.key(3), (2, 80), 0, 96)
    params = model.init(jax.random.key(0), tokens)["params"]
    assert model.remat_blocks(params, tokens.shape) == 0
    loss = make_loss_fn(model.apply)
    fn = jax.jit(jax.value_and_grad(
        lambda p, x: loss(p, x, x, jnp.ones((2,)))[0]))
    kept = fn(params, tokens)
    _no_room(monkeypatch)
    n = len(model._layers())
    assert model.remat_blocks(params, tokens.shape) == n
    recomputed = jax.jit(jax.value_and_grad(
        lambda p, x: loss(p, x, x, jnp.ones((2,)))[0]))(params, tokens)
    np.testing.assert_allclose(kept[0], recomputed[0], rtol=1e-6)
    if name in _SCALED:
        tree_close(kept[1], recomputed[1], _SCALED[name])
        return
    for a, b in zip(jax.tree_util.tree_leaves(kept[1]),
                    jax.tree_util.tree_leaves(recomputed[1])):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("name", sorted(_MODELS))
def test_a_longer_step_keeps_fewer_blocks(name, monkeypatch):
    """The count follows the tokens a step: with room for the blocks at
    one length, twice the length keeps fewer."""
    model = _model(name, "bfloat16")
    tokens = jnp.zeros((1, 64), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), tokens)["params"])
    n = len(model._layers())
    seen = []
    monkeypatch.setattr(parts, "leading_remat",
                        lambda n, b, room: seen.append(tuple(b)) or 0)
    model.remat_blocks(params, (1, 64))
    model.remat_blocks(params, (1, 128))
    monkeypatch.undo()
    short, long = seen
    assert len(short) == n and all(0 < a < b for a, b in zip(short, long))
    # A room of the short step's blocks keeps them all there, and fewer
    # at twice the length.
    assert parts.leading_remat(n, short, sum(short)) == 0
    assert parts.leading_remat(n, long, sum(short)) > 0


def test_remat_blocks_reaches_the_recorder(tmp_path, monkeypatch):
    """``run_simulation`` records the model's count at one local step's
    tokens as ``remat_blocks``: every block of four with no room."""
    from distributed_learning_simulator_tpu.config import get_config
    from distributed_learning_simulator_tpu.simulator import run_simulation
    from distributed_learning_simulator_tpu.telemetry import spans

    _no_room(monkeypatch)
    config = get_config([
        "--dataset_name", "tokens", "--model_name", "olmo_hybrid",
        "--model_args", json.dumps(share_args()),
        "--worker_number", "4", "--epoch", "1", "--batch_size", "1",
        "--round", "1", "--client_chunk_size", "1",
        "--pipeline_rounds", "false", "--eval_batch_size", "1",
        "--optimizer_name", "sgd", "--learning_rate", "0.3",
        "--momentum", "0", "--distributed_algorithm", "fed",
        "--telemetry_level", "basic", "--log_root", str(tmp_path / "log"),
        "--compilation_cache_dir", "none",
    ])
    run_simulation(config, dataset=_token_dataset())
    assert spans.last_run().counters()["remat_blocks"] == 4
