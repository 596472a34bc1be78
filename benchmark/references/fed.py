"""Plain reference: FedAvg rounds (McMahan et al. 2017), client by client.

One round: every client starts from the global parameters, runs
``epochs`` passes of minibatch SGD with momentum over its own shard
(fresh momentum every round), and the server replaces the global
parameters by the mean of the clients' parameters weighted by shard size.
After every round the server evaluates the global model on the test set.

What makes this the *same experiment* as the program's, and is therefore
spelled out here rather than imported: the random stream that orders each
client's minibatches (``key(seed + 1)``, split once per round; the round
key split four ways, the second part split per client, that split per
epoch, and the epoch key permuting the shard), SGD with heavy-ball
momentum and no dampening, softmax cross-entropy averaged over a
minibatch's real samples, a client's reported loss being the mean over
its last epoch's steps. Everything is float32; products go through the
two primitives of :func:`primitives`, at ``highest`` precision for the
reference. Clients are trained in blocks so the working set fits beside
nothing else on one chip. Imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_DIMS = ("NHWC", "HWIO", "NHWC")
# The next precision down from the one a configuration states, which is
# what a control computes in (PERF.md § 2).
NEXT_LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def primitives(precision: str):
    """``(conv, dense, store)`` computing in ``precision``.

    ``float32`` is the reference: f32 operands, ``highest`` (a TPU
    otherwise multiplies f32 in bf16 passes). The others round both
    operands and the result of every product to that type
    (straight-through, so the backward pass sees the rounded operands
    too) and accumulate in f32: the arithmetic of a matrix unit fed that
    type; ``store`` rounds what a client keeps between steps.
    """
    if precision == "float32":
        def q(a):
            return a
    else:
        info = jnp.finfo(jnp.dtype(precision))
        bits = (info.nexp, info.nmant)

        # ``reduce_precision``, not a pair of casts: XLA on the TPU elides
        # f32 -> narrow -> f32 round trips (``xla_allow_excess_precision``),
        # which left the first controls of PR 24 computing in f32.
        def q(a):
            return a + jax.lax.stop_gradient(
                jax.lax.reduce_precision(a, *bits) - a
            )

    hi = jax.lax.Precision.HIGHEST

    def conv(x, kernel, stride, padding):
        return q(jax.lax.conv_general_dilated(
            q(x), q(kernel), (stride, stride), padding,
            dimension_numbers=_DIMS, precision=hi,
        ))

    def dense(x, kernel):
        return q(jnp.dot(q(x), q(kernel), precision=hi))

    return conv, dense, q


def _nll(logits, y):
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]


def _decode(u8, sample_shape):
    return (u8.astype(jnp.float32) / 255.0).reshape(
        (u8.shape[0],) + tuple(sample_shape)
    )


def make_block_fn(apply, spec: dict, store=lambda a: a):
    """``block(global, xs, ys, mask, keys, weights, odd) -> ((even, odd),
    sum_i loss_i)`` for a block of clients, jitted: ``sum_i w_i p_i``
    over the block's even-numbered and its odd-numbered clients."""
    lr, mu = spec["learning_rate"], spec["momentum"]
    batch, epochs = spec["batch_size"], spec["epochs"]
    shape = spec["sample_shape"]

    def loss_fn(params, bx, by, bm):
        nll = _nll(apply(params, bx), by)
        return jnp.sum(nll * bm) / jnp.maximum(jnp.sum(bm), 1.0)

    def local_train(params, xs, ys, mask, key):
        steps = xs.shape[0] // batch
        velocity = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses = None
        for epoch_key in jax.random.split(key, epochs):
            perm = jax.random.permutation(epoch_key, xs.shape[0])

            def step(carry, i):
                p, v = carry
                idx = jax.lax.dynamic_slice_in_dim(perm, i * batch, batch)
                loss, g = jax.value_and_grad(loss_fn)(
                    p, _decode(xs[idx], shape), ys[idx], mask[idx]
                )
                v = jax.tree_util.tree_map(
                    lambda g, v: store(store(g) + mu * v), g, v
                )
                p = jax.tree_util.tree_map(
                    lambda p, v: store(p - lr * v), p, v
                )
                return (p, v), loss

            (params, velocity), losses = jax.lax.scan(
                step, (params, velocity), jnp.arange(steps)
            )
        return params, jnp.mean(losses)

    @jax.jit
    def block(global_params, xs, ys, mask, keys, weights, odd):
        trained, losses = jax.vmap(
            local_train, in_axes=(None, 0, 0, 0, 0)
        )(global_params, xs, ys, mask, keys)
        # Two sums, over the block's even-numbered and odd-numbered
        # clients (``odd``: 0.0 or 1.0 each): the groups the comparison's
        # named subsets are made of.
        wsums = tuple(
            jax.tree_util.tree_map(
                lambda p: jnp.tensordot(w, p, axes=(0, 0)), trained
            )
            for w in (weights * (1.0 - odd), weights * odd)
        )
        return wsums, jnp.sum(losses)

    return block


def make_eval_fn(apply, spec: dict):
    shape = spec["sample_shape"]

    @jax.jit
    def nll_sum(params, xs, ys):
        return jnp.sum(_nll(apply(params, _decode(xs, shape)), ys))

    def evaluate(params, x_test, y_test):
        total = 0.0
        step = spec["eval_block"]
        for i in range(0, x_test.shape[0], step):
            total += float(nll_sum(params, x_test[i:i + step], y_test[i:i + step]))
        return total / x_test.shape[0]

    return evaluate


def subsets(parts: int) -> dict:
    """The named subsets of clients whose mean the comparison can tell
    from the full mean, as lists of groups. A group is the even-numbered
    or the odd-numbered clients of one of ``parts`` consecutive runs of
    clients (group ``2 * part + parity``). Named: every run (one chip's
    share of a ``parts``-chip mesh), the first and the second half of the
    runs, and the even-numbered and the odd-numbered clients."""
    named = {f"run_{k}": [2 * k, 2 * k + 1] for k in range(parts)}
    if parts % 2 == 0:
        half = parts
        named["first_half"] = list(range(half))
        named["second_half"] = list(range(half, 2 * parts))
    named["even"] = list(range(0, 2 * parts, 2))
    named["odd"] = list(range(1, 2 * parts, 2))
    return named


def start_loss(forward, model: dict, params0, data: dict, spec: dict):
    """The server's test loss at the starting weights: what a program
    that hands its state back unchanged keeps reporting."""
    conv, dense, _ = primitives("float32")
    apply = functools.partial(forward, model, conv=conv, dense=dense)
    params = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), params0
    )
    return make_eval_fn(apply, spec)(
        params, jnp.asarray(data["x_test"]), jnp.asarray(data["y_test"])
    )


def run(forward, model: dict, params0, data: dict, spec: dict,
        precision: str = "float32", state_precision: str | None = None,
        parts: int = 4) -> dict:
    """Follow ``spec["rounds"]`` rounds from ``params0``.

    ``data``: ``x`` uint8 ``[clients, shard, dim]``, ``y``, ``mask``,
    ``sizes``, ``x_test`` uint8 ``[n, dim]``, ``y_test``. Returns per-round
    ``test_loss`` and ``client_loss``, the global parameters after the
    last round (``params``), and that round's aggregate as partial sums
    over groups of clients (``group_sums``, ``group_weights``; the groups
    and the named ``subsets`` made of them: :func:`subsets`), from which
    the comparison's matched filter and the planted faults are read.

    ``precision`` is what products and activations are computed in, and
    ``state_precision`` (default: the same) what a client's parameters,
    gradients and momentum are stored in: a control lowers both, or the
    products alone.
    """
    conv, dense, store = primitives(precision)
    if state_precision is not None:
        store = primitives(state_precision)[2]
    apply = functools.partial(forward, model, conv=conv, dense=dense)
    block_fn = make_block_fn(apply, spec, store)
    evaluate = make_eval_fn(apply, spec)
    n = data["x"].shape[0]
    step = spec["block_clients"]
    if n % parts or (n // parts) % step:
        raise ValueError(
            f"{n} clients do not cut into {parts} parts of whole blocks "
            f"of {step}"
        )
    xs, ys, mask = (jnp.asarray(data[k]) for k in ("x", "y", "mask"))
    weights = jnp.asarray(data["sizes"], jnp.float32)
    odd = (jnp.arange(n) % 2).astype(jnp.float32)
    x_test, y_test = jnp.asarray(data["x_test"]), jnp.asarray(data["y_test"])
    params = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), params0
    )
    key = jax.random.key(spec["seed"] + 1)
    out = {"test_loss": [], "client_loss": []}
    for _ in range(spec["rounds"]):
        key, round_key = jax.random.split(key)
        train_key = jax.random.split(round_key, 4)[1]
        client_keys = jax.random.split(train_key, n)
        sums = [None] * (2 * parts)
        loss_sum = 0.0
        for start in range(0, n, step):
            sl = slice(start, start + step)
            wsums, lsum = block_fn(
                params, xs[sl], ys[sl], mask[sl], client_keys[sl],
                weights[sl], odd[sl],
            )
            part = start * parts // n
            for parity, wsum in enumerate(wsums):
                g = 2 * part + parity
                sums[g] = wsum if sums[g] is None else (
                    jax.tree_util.tree_map(jnp.add, sums[g], wsum)
                )
            loss_sum = loss_sum + lsum
        total = functools.reduce(
            lambda a, b: jax.tree_util.tree_map(jnp.add, a, b), sums
        )
        params = jax.tree_util.tree_map(
            lambda s: s / jnp.sum(weights), total
        )
        out["client_loss"].append(float(loss_sum) / n)
        out["test_loss"].append(evaluate(params, x_test, y_test))
    out["params"] = jax.tree_util.tree_map(np.asarray, params)
    out["group_sums"] = [jax.tree_util.tree_map(np.asarray, s) for s in sums]
    group_of = 2 * (np.arange(n) * parts // n) + np.arange(n) % 2
    sizes = np.asarray(data["sizes"], np.float64)
    out["group_weights"] = [
        float(sizes[group_of == g].sum()) for g in range(2 * parts)
    ]
    out["subsets"] = subsets(parts)
    return out
