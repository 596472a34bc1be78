"""Client-axis training engine: loss decreases, masking works, eval is exact."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_learning_simulator_tpu.models.registry import get_model, init_params
from distributed_learning_simulator_tpu.parallel import engine
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


# --- short local runs are compiled unrolled (UNROLL_MAX_LOCAL_STEPS) --------

_UNROLL_MAX = engine.UNROLL_MAX_LOCAL_STEPS
_BATCH = 4


def _short_run(steps, epochs=1, momentum=0.9, reset=True, bf16=False,
               stats=False):
    """A jitted ``local_train`` on a 6-feature MLP and its arguments:
    ``steps`` minibatches of ``_BATCH`` an epoch. Whether the scans unroll
    is decided when the function is first traced."""
    model = get_model("mlp", num_classes=4)
    params = init_params(model, np.zeros((1, 6), np.float32))
    opt = make_optimizer("SGD", 0.05, momentum=momentum)
    local_train = make_local_train_fn(
        model.apply, opt, local_epochs=epochs, batch_size=_BATCH,
        reset_optimizer=reset,
        compute_dtype=jnp.bfloat16 if bf16 else None, collect_stats=stats,
    )
    rng = np.random.default_rng(steps)
    n = steps * _BATCH
    state_params = params
    if bf16:
        state_params = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16), params
        )
    # A persistent optimizer state that is not zeros: with
    # reset_optimizer=False the first step's momentum is an input.
    opt_state = jax.tree_util.tree_map(
        lambda s: s + jnp.asarray(0.25, s.dtype), opt.init(state_params)
    )
    args = (
        params, opt_state,
        jnp.asarray(rng.normal(size=(n, 6)).astype(np.float32)),
        jnp.asarray(rng.integers(0, 4, size=n).astype(np.int32)),
        jnp.ones(n), jax.random.key(3),
    )
    return jax.jit(local_train), args


@pytest.mark.parametrize("stats", [False, True], ids=["nostats", "stats"])
@pytest.mark.parametrize("bf16", [False, True], ids=["f32", "bf16sr"])
@pytest.mark.parametrize("reset", [True, False], ids=["reset", "keep"])
@pytest.mark.parametrize("momentum", [0.0, 0.9])
@pytest.mark.parametrize("epochs", [1, 2])
@pytest.mark.parametrize("steps", sorted({1, 2, _UNROLL_MAX, _UNROLL_MAX + 1}))
def test_unrolled_local_train_matches_scanned(
    monkeypatch, steps, epochs, momentum, reset, bf16, stats
):
    """The unrolled and the scanned form of ``local_train`` are one
    computation: parameters, returned optimizer state and metrics agree
    on the same inputs. f32 state: exactly, the same operations run in
    the same order. bf16 state with stochastic rounding: to two bf16 ulps
    (2**-6 relative). The dither is a hash of the f32 sum's bits, so a
    last-bit difference in that sum (XLA may keep an f32 intermediate
    where the scan's carry rounds it to bf16) flips a rounding decision
    and moves that weight one ulp in each of the two steps; on today's
    CPU backend the difference is 0. A run above the constant is the
    bypass: it lowers to the same program text whatever the constant."""
    kw = dict(epochs=epochs, momentum=momentum, reset=reset, bf16=bf16,
              stats=stats)
    unrolled = engine.local_steps_unrolled(epochs, steps)
    assert unrolled == (epochs * steps if epochs * steps <= _UNROLL_MAX else 0)
    fn, args = _short_run(steps, **kw)
    got = fn(*args) if unrolled else fn.lower(*args).as_text()
    monkeypatch.setattr(engine, "UNROLL_MAX_LOCAL_STEPS", 0)
    twin, _ = _short_run(steps, **kw)
    if not unrolled:
        assert got == twin.lower(*args).as_text()
        return
    want = twin(*args)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype
        a, b = (np.asarray(v, dtype=np.float32) for v in (a, b))
        if bf16:
            np.testing.assert_allclose(a, b, rtol=2.0 ** -6, atol=1e-6)
        else:
            np.testing.assert_array_equal(a, b)


def test_unrolled_lowering_traces_the_step_once():
    """What guards the first round's tracing and lowering: unrolled, the
    step body is still lowered once (a ``closed_call`` called twice), so
    the module holds as many matmuls as the scanned form; only the step
    loop's ``stablehlo.while`` is gone (the length-1 epoch scan never had
    one) and the parameters pass a barrier at the step's entry, where
    the loop carried them. One step above the constant the loop is
    there and the barrier is not."""
    def counts(steps, unroll_max):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(engine, "UNROLL_MAX_LOCAL_STEPS", unroll_max)
            fn, args = _short_run(steps)
            text = fn.lower(*args).as_text()
        return (text.count("stablehlo.while"), text.count("dot_general"),
                text.count("stablehlo.optimization_barrier"))

    whiles, dots, barriers = counts(2, _UNROLL_MAX)
    scanned_whiles, scanned_dots, scanned_barriers = counts(2, 0)
    assert (whiles, dots) == (scanned_whiles - 1, scanned_dots)
    assert (barriers, scanned_barriers) == (1, 0)  # in the one step body
    assert counts(_UNROLL_MAX + 1, _UNROLL_MAX) == counts(_UNROLL_MAX + 1, 0)
    assert counts(_UNROLL_MAX + 1, 0) == (scanned_whiles, scanned_dots, 0)


def _compiled_momentum_program(reset, unroll_max):
    """Compiled (CPU) HLO text of a 2-step momentum run, and how many of
    its instructions are ``optimizer.init``'s zeros: a scalar broadcast to
    a parameter's shape traced directly under ``local_train``."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine, "UNROLL_MAX_LOCAL_STEPS", unroll_max)
        fn, args = _short_run(2, momentum=0.9, reset=reset)
        text = fn.lower(*args).compile().as_text()
    init_zeros = [
        line for line in text.splitlines()
        if 'op_name="jit(local_train)/broadcast_in_dim"' in line
        and " broadcast(" in line
    ]
    return text, len(init_zeros)


def test_unrolled_momentum_folds_the_zero_state():
    """The mechanism itself, on the compiled program: with a fresh
    optimizer and two unrolled steps XLA sees the first step's momentum
    is zeros, so no zeros of a parameter's shape are made and the decay
    multiplies once a leaf (step 2) — not twice, as it does when the
    first state is an input, and not through a loop-carried zero state,
    as in the scanned form."""
    n_leaves = 4  # two Dense layers: kernel and bias each
    text, zeros = _compiled_momentum_program(True, _UNROLL_MAX)
    assert zeros == 0
    assert text.count("constant(0.9)") == n_leaves
    kept, _ = _compiled_momentum_program(False, _UNROLL_MAX)
    assert kept.count("constant(0.9)") == 2 * n_leaves
    _, scanned_zeros = _compiled_momentum_program(True, 0)
    assert scanned_zeros > 0  # the check sees them where they exist
