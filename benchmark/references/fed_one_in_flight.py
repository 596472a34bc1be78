"""Plain reference: FedAvg rounds with ONE client's state on the device.

The experiment is ``fed.py``'s, to the letter: the same random stream
(``key(seed + 1)`` split once a round, the round key four ways, the second
part per client, that per epoch, the epoch key permuting the shard), the
same minibatch SGD with heavy-ball momentum, the same weighted mean, the
same evaluation after every round (``fed.py``'s own ``primitives``, loaded
from the file beside this one; its ``make_eval_fn`` spelled out again
here for the sake of a compile option and of compiling it beside the step). What differs is
where things live, for a model of hundreds of millions of parameters:
``fed.py`` keeps a vmapped block of trained copies, gradients, momentum
and eight whole-tree group sums on the device (about 48 bytes a
parameter); here the device holds the client in training (its parameters,
its gradient and the step's temporaries) and ONE more copy of the model:
the client before, on its way to the host, then the next client's
starting copy, on its way up (15 GB at 841 M parameters). The global model
and the weighted sum live on the host in numpy; each client is added in,
leaf by leaf, while the device trains the next. Float32 throughout.

Subsets. ``harness/compare.py`` builds, for the program, for the
reference and for every named subset, a float64 copy of every parameter,
plus two more inside ``client_share_gap``: 8 bytes a parameter each. At
841 M parameters one subset already needs 50 GB of host memory, which a
one-chip machine (40 GiB) does not have. So :func:`run` names NO subset
(``client_share_gap`` reads 0). A client left out is caught by
``update_direction`` instead, which the planted faults show:
``subset_sums=True`` also keeps the last round's weighted sums over the first half of the clients
and over the odd-numbered ones (``fed.subsets(2)``'s ``first_half`` and
``odd``), from which ``calibrate_one_in_flight.py`` puts each mean in the
program's place, one at a time.
"""

from __future__ import annotations

import ctypes
import functools
import importlib.util
import os
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np


def _beside(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


fed = _beside("fed")
NEXT_LOWER = fed.NEXT_LOWER
# The reference's two programs are compiled for a short compile rather
# than the shortest run: at the compiler's default effort the f32 step of
# this model takes 143 s to compile on the chip's host and the evaluation
# 35 s, of the 360 s a whole run may take; at -0.5 the step takes 19 s
# (compiled for a described v5e; -0.1 still takes 107 s, -1.0 16 s but runs
# 2.3 times longer; PERF.md § 6). The arithmetic is the same.
FAST_COMPILE = {"exec_time_optimization_effort": -0.5}


def release_heap():
    """Hand the heap's freed pages back to the system. A compile of a
    minute or two allocates and frees gigabytes that glibc keeps in its
    arenas (9 GB after the step's compile at the default effort), and
    ``harness/compare.py`` next builds three float64 copies of the model
    on a host of 40 GiB."""
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass


def make_step_fn(apply, spec: dict, store):
    """One minibatch step of one client, jitted; the client's parameters
    (and momentum) are donated, so the device holds one copy and the
    gradient."""
    lr, mu = spec["learning_rate"], spec["momentum"]
    task, data_spec = spec["task"], spec["data"]

    def loss_fn(params, bx, by, bm):
        total, count = task.loss(apply(params, bx), by, bm)
        return total / jnp.maximum(count, 1.0)

    @functools.partial(jax.jit, donate_argnums=(0, 1),
                       compiler_options=FAST_COMPILE)
    def step(params, velocity, xb, yb, mb):
        loss, g = jax.value_and_grad(loss_fn)(
            params, task.decode(xb, data_spec), yb, mb
        )
        if velocity is None:  # no momentum: v = g
            velocity_out, v = None, jax.tree_util.tree_map(store, g)
        else:
            v = velocity_out = jax.tree_util.tree_map(
                lambda g, v: store(store(g) + mu * v), g, velocity
            )
        params = jax.tree_util.tree_map(
            lambda p, v: store(p - lr * v), params, v
        )
        return params, velocity_out, loss

    return step


def make_loss_sum_fn(apply, spec: dict):
    """The task's loss summed over one block of test samples, and its
    count of real targets, jitted with :data:`FAST_COMPILE`."""
    task, data_spec = spec["task"], spec["data"]

    @functools.partial(jax.jit, compiler_options=FAST_COMPILE)
    def loss_sum(params, xs, ys):
        return task.loss(apply(params, task.decode(xs, data_spec)), ys)

    return loss_sum


def evaluate(loss_sum, params, x_test, y_test, block: int):
    """``fed.make_eval_fn``'s evaluation: ``loss_sum`` over the test set
    in blocks of ``block`` samples, over its real targets."""
    total, count = 0.0, 0.0
    for i in range(0, x_test.shape[0], block):
        block_total, block_count = loss_sum(
            params, x_test[i:i + block], y_test[i:i + block]
        )
        total += float(block_total)
        count += float(block_count)
    return total / count


def compile_together(*jobs):
    """``jitted.lower(*args).compile()`` for every ``(jitted, args)``, at
    the same time (the compiler runs outside the interpreter's lock):
    the step takes 19 s and the evaluation 6 s of a run's 360 s."""
    with ThreadPoolExecutor(max_workers=len(jobs)) as pool:
        futures = [
            pool.submit(lambda fn=fn, args=args: fn.lower(*args).compile())
            for fn, args in jobs
        ]
        return [f.result() for f in futures]


def start_loss(forward, model: dict, params0, data: dict, spec: dict):
    """``fed.start_loss`` through this file's evaluation (its compile
    option): the server's test loss at the starting weights."""
    apply = functools.partial(forward, model, **fed.primitives("float32"))
    params = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), params0
    )
    return evaluate(
        make_loss_sum_fn(apply, spec), params, jnp.asarray(data["x_test"]),
        jnp.asarray(data["y_test"]), spec["eval_block"],
    )


def _add_scaled(acc, leaf, weight):
    """``acc += weight * leaf`` on the host, a slice at a time: no third
    array of the leaf's size is made (a fetched leaf is read-only)."""
    a, p, piece = acc.reshape(-1), leaf.reshape(-1), 1 << 24
    for i in range(0, a.size, piece):
        a[i:i + piece] += p[i:i + piece] * weight


def _zeros_on_host(tree):
    return jax.tree_util.tree_map(
        lambda a: np.zeros(np.shape(a), np.float32), tree
    )


def run(forward, model: dict, params0, data: dict, spec: dict,
        precision: str = "float32", state_precision: str | None = None,
        subset_sums: bool = False) -> dict:
    """Follow ``spec["rounds"]`` rounds from ``params0``: ``fed.run``'s
    arguments and results, with no group and no subset named.
    ``subset_sums`` adds ``subset_sums`` and ``subset_weights``, the last
    round's weighted parameter sums over ``first_half`` and ``odd``
    (module docstring)."""
    products = fed.primitives(precision)
    store = fed.primitives(state_precision or precision)["q"]
    apply = functools.partial(forward, model, **products)
    n = data["x"].shape[0]
    batch, epochs, mu = spec["batch_size"], spec["epochs"], spec["momentum"]
    block = spec["eval_block"]
    sizes = np.asarray(data["sizes"], np.float32)
    x_test, y_test = jnp.asarray(data["x_test"]), jnp.asarray(data["y_test"])
    if x_test.shape[0] % block:
        raise ValueError(f"{x_test.shape[0]} test samples in blocks of {block}")
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.float32), params0
    )
    velocity_shapes = None if mu == 0 else shapes
    step_fn, loss_sum = compile_together(
        (make_step_fn(apply, spec, store),
         (shapes, velocity_shapes,
          *(data[k][0][:batch] for k in ("x", "y", "mask")))),
        (make_loss_sum_fn(apply, spec),
         (shapes, x_test[:block], y_test[:block])),
    )
    members = {
        "first_half": np.arange(n) < n // 2,
        "odd": np.arange(n) % 2 == 1,
    } if subset_sums else {}
    sums = {name: _zeros_on_host(params0) for name in members}
    total = _zeros_on_host(params0)
    global_host = params0
    release_heap()  # what the compiles left, the program's and these
    key = jax.random.key(spec["seed"] + 1)
    out = {"test_loss": [], "client_loss": []}

    def upload(tree):
        return jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), tree
        )

    for round_index in range(spec["rounds"]):
        last = round_index == spec["rounds"] - 1
        key, round_key = jax.random.split(key)
        train_key = jax.random.split(round_key, 4)[1]
        client_keys = jax.random.split(train_key, n)
        for leaf in jax.tree_util.tree_leaves(total):
            leaf.fill(0.0)  # the round before's
        losses_of, leaving = [], None

        def land(client, params):
            """The trained client, by now (nearly) on the host, is added
            into the sums leaf by leaf and leaves the device: never a
            whole second model on the host."""
            weight = np.float32(sizes[client])
            into = [total] + [
                sums[name] for name, inside in members.items()
                if last and inside[client]
            ]
            for leaf, *targets in zip(*(
                    jax.tree_util.tree_leaves(t) for t in [params] + into)):
                for target in targets:
                    _add_scaled(target, np.asarray(leaf), weight)
                leaf.delete()

        # The device is never without work: a client's steps are queued
        # before the client before is waited for, and the next starting
        # copy goes up only once that one has left (three copies and a
        # step's temporaries do not fit).
        arriving = upload(global_host)
        for c in range(n):
            params, arriving = arriving, None
            velocity = None if mu == 0 else jax.tree_util.tree_map(
                jnp.zeros_like, params
            )
            xs, ys, mask = (data[k][c] for k in ("x", "y", "mask"))
            losses = []
            for epoch_key in jax.random.split(client_keys[c], epochs):
                perm = np.asarray(
                    jax.random.permutation(epoch_key, xs.shape[0])
                )
                losses = []
                for i in range(xs.shape[0] // batch):
                    idx = perm[i * batch:(i + 1) * batch]
                    params, velocity, loss = step_fn(
                        params, velocity, xs[idx], ys[idx], mask[idx]
                    )
                    losses.append(loss)
            losses_of.append(losses)
            del velocity
            if leaving is not None:
                land(*leaving)
                leaving = None
            if c + 1 < n:
                arriving = upload(global_host)
            for leaf in jax.tree_util.tree_leaves(params):
                leaf.copy_to_host_async()
            leaving = (c, params)
            del params
        land(*leaving)
        del leaving
        loss_sum_clients = sum(
            float(np.mean([float(v) for v in losses]))
            for losses in losses_of
        )
        weight = np.float32(sizes.sum())
        global_host = jax.tree_util.tree_map(lambda s: s / weight, total)
        out["client_loss"].append(loss_sum_clients / n)
        out["test_loss"].append(evaluate(
            loss_sum, upload(global_host), x_test, y_test, block
        ))
    out["params"] = global_host
    out["group_sums"], out["group_weights"], out["subsets"] = [], [], {}
    if subset_sums:
        out["subset_sums"] = sums
        out["subset_weights"] = {
            name: float(sizes[inside].sum())
            for name, inside in members.items()
        }
    del total
    release_heap()
    return out
