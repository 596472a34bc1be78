"""Client-axis training engine: loss decreases, masking works, eval is exact."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_learning_simulator_tpu.models.registry import get_model, init_params
from distributed_learning_simulator_tpu.parallel.engine import (
    make_eval_fn,
    make_local_train_fn,
    make_loss_fn,
    make_optimizer,
    make_reshaper,
    pad_eval_set,
)


def _setup(tiny_dataset):
    model = get_model("mlp", num_classes=tiny_dataset.num_classes)
    params = init_params(model, tiny_dataset.x_train[:1])
    return model, params


def test_local_train_reduces_loss(tiny_dataset):
    model, params = _setup(tiny_dataset)
    opt = make_optimizer("SGD", 0.1)
    local_train = make_local_train_fn(model.apply, opt, local_epochs=3,
                                      batch_size=32)
    xs = jnp.asarray(tiny_dataset.x_train[:256])
    ys = jnp.asarray(tiny_dataset.y_train[:256])
    mask = jnp.ones(256)
    loss_fn = make_loss_fn(model.apply)
    loss_before, _ = loss_fn(params, xs, ys, mask)
    opt_state = opt.init(params)
    new_params, _, metrics = jax.jit(local_train)(
        params, opt_state, xs, ys, mask, jax.random.key(0)
    )
    loss_after, _ = loss_fn(new_params, xs, ys, mask)
    assert float(loss_after) < float(loss_before)
    assert np.isfinite(float(metrics["loss"]))


def test_masked_samples_do_not_contribute(tiny_dataset):
    """Training with garbage in masked-out rows == training without them."""
    model, params = _setup(tiny_dataset)
    opt = make_optimizer("SGD", 0.1)
    local_train = jax.jit(
        make_local_train_fn(model.apply, opt, local_epochs=1, batch_size=32)
    )
    xs = np.array(tiny_dataset.x_train[:64])
    ys = np.array(tiny_dataset.y_train[:64])
    mask = np.ones(64, np.float32)
    mask[32:] = 0.0
    xs_garbage = xs.copy()
    xs_garbage[32:] = 999.0
    ys_garbage = ys.copy()
    ys_garbage[32:] = 0

    opt_state = opt.init(params)
    p1, _, _ = local_train(params, opt_state, jnp.asarray(xs),
                           jnp.asarray(ys), jnp.asarray(mask), jax.random.key(1))
    p2, _, _ = local_train(params, opt_state, jnp.asarray(xs_garbage),
                           jnp.asarray(ys_garbage), jnp.asarray(mask),
                           jax.random.key(1))
    for a, b in zip(jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_eval_fn_matches_numpy(tiny_dataset):
    model, params = _setup(tiny_dataset)
    xb, yb, mb = pad_eval_set(tiny_dataset.x_test, tiny_dataset.y_test, 100)
    out = jax.jit(make_eval_fn(model.apply))(
        params, jnp.asarray(xb), jnp.asarray(yb), jnp.asarray(mb)
    )
    logits = model.apply({"params": params},
                         jnp.asarray(tiny_dataset.x_test))
    acc = float((np.argmax(np.asarray(logits), 1) ==
                 tiny_dataset.y_test).mean())
    np.testing.assert_allclose(float(out["accuracy"]), acc, atol=1e-6)


def test_pad_eval_set_shapes():
    x = np.zeros((10, 3, 3, 1), np.float32)
    y = np.zeros((10,), np.int32)
    xb, yb, mb = pad_eval_set(x, y, 4)
    assert xb.shape == (3, 4, 3, 3, 1)
    assert mb.sum() == 10


def test_flattened_eval_matches_unflattened(tiny_dataset):
    """Flat eval storage + in-program reshape (the TPU layout path) must give
    identical metrics to direct NHWC batches."""
    model, params = _setup(tiny_dataset)
    direct = pad_eval_set(tiny_dataset.x_test, tiny_dataset.y_test, 100)
    out1 = jax.jit(make_eval_fn(model.apply))(
        params, *(jnp.asarray(a) for a in direct)
    )
    flat = pad_eval_set(tiny_dataset.x_test, tiny_dataset.y_test, 100,
                        flatten=True)
    assert flat[0].ndim == 3  # [n_batches, batch, prod(sample_shape)]
    reshaper = make_reshaper(tiny_dataset.x_test.shape[1:])
    out2 = jax.jit(make_eval_fn(model.apply, preprocess=reshaper))(
        params, *(jnp.asarray(a) for a in flat)
    )
    np.testing.assert_allclose(
        float(out1["accuracy"]), float(out2["accuracy"]), atol=1e-6
    )
    np.testing.assert_allclose(
        float(out1["loss"]), float(out2["loss"]), atol=1e-5
    )


@pytest.mark.parametrize("n,chunk,shards", [
    (24, 8, 4),    # even split: 2 clients of every shard per chunk
    (24, 10, 4),   # chunk not a multiple of shards, per-shard remainder
    (16, 3, 8),    # chunk smaller than the shard count
    (10, 4, 4),    # cohort does not divide into shards: consecutive chunks
])
def test_chunked_accumulate_shard_local_chunks(n, chunk, shards):
    """Chunks that take their clients from every shard (the mesh path)
    give the same reduction and the same per-client results, in client
    order, as consecutive chunks; only the grouping differs."""
    from distributed_learning_simulator_tpu.parallel.engine import (
        chunked_accumulate,
    )

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    w = jnp.asarray(rng.uniform(size=(n,)).astype(np.float32))
    seen = []

    def compute(trees, _key):
        xc, wc, none = trees
        assert none is None
        seen.append(xc.shape[0])
        return jnp.tensordot(wc, xc, axes=(0, 0)), (xc * 2.0, None)

    def run(shards):
        seen.clear()
        return chunked_accumulate(
            (x, w, None), chunk, compute, jnp.zeros(3), shards=shards
        )

    acc1, (per1, _) = run(1)
    acc, (per, _) = run(shards)
    assert max(seen) <= max(chunk, shards)  # clients in flight stay bounded
    np.testing.assert_allclose(acc, acc1, rtol=1e-5)
    np.testing.assert_allclose(acc, np.asarray(w) @ np.asarray(x), rtol=1e-5)
    np.testing.assert_array_equal(per, per1)
    np.testing.assert_array_equal(per, np.asarray(x) * 2.0)
