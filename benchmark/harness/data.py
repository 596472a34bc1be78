"""Inputs and weights from ``--seed``: the benchmark makes both.

The data is a seeded surrogate with a real set's shapes (there is no
network): per-class Gaussian prototypes plus noise, learnable so the loss
moves like training's. The recipe is a copy of the program's own offline
surrogate (``data/registry._synthetic_classification``; PERF.md § 7 lists
the original for a later PR to delete) with one change: pixels are made
as 8-bit values, as image sets are stored, so the program's compact
uint8 client storage holds them exactly and the plain reference trains on
the very same numbers.

The weights are drawn once, on the device, in one jitted call, from the
layout the configuration's plain reference declares, and handed to the
program as the checkpoint it resumes from: neither side takes weights the
other has made.
"""

from __future__ import annotations

import numpy as np

DIFFICULTY = 0.75


def make_data(seed: int, shape, classes: int, n_train: int,
              n_test: int) -> dict:
    """``x_train``/``x_test`` uint8 ``[n, prod(shape)]``, labels int32."""
    rng = np.random.default_rng(seed)
    dim = int(np.prod(shape))
    prototypes = rng.standard_normal((classes, dim), dtype=np.float32)

    def make(n, sub_seed):
        lrng = np.random.default_rng(sub_seed)
        y = lrng.integers(0, classes, size=n).astype(np.int32)
        x = lrng.standard_normal((n, dim), dtype=np.float32)
        x *= DIFFICULTY
        x += prototypes[y] * (1.0 - DIFFICULTY)
        x *= 127.5
        x += 127.5
        np.clip(x, 0.0, 255.0, out=x)
        return np.rint(x).astype(np.uint8), y

    x_train, y_train = make(n_train, seed + 1)
    x_test, y_test = make(n_test, seed + 2)
    return {
        "shape": tuple(shape), "classes": classes,
        "x_train": x_train, "y_train": y_train,
        "x_test": x_test, "y_test": y_test,
    }


def iid_clients(data: dict, n_clients: int, seed: int) -> dict:
    """Equal IID shards, one per client: sample ``perm[i*s:(i+1)*s]`` of a
    seeded permutation goes to client ``i`` — the partition rule of the
    experiment (the program's ``iid`` partition draws the same one)."""
    perm = np.random.default_rng(seed).permutation(len(data["y_train"]))
    shard = len(perm) // n_clients
    idx = perm[: shard * n_clients].reshape(n_clients, shard)
    return {
        "x": data["x_train"][idx], "y": data["y_train"][idx],
        "mask": np.ones((n_clients, shard), np.float32),
        "sizes": np.full((n_clients,), float(shard), np.float32),
        "x_test": data["x_test"], "y_test": data["y_test"],
    }


def init_params(layout: dict, seed: int):
    """Nested dict of f32 arrays: ``kernel`` leaves normal with variance
    ``1 / fan_in``, ``ones``/``zeros`` as named. One jitted call."""
    import jax
    import jax.numpy as jnp

    paths = sorted(layout)

    @jax.jit
    def draw(key):
        leaves = []
        for i, path in enumerate(paths):
            shape, kind = layout[path]
            if kind == "kernel":
                fan_in = int(np.prod(shape[:-1]))
                leaf = jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32
                ) / np.sqrt(fan_in)
            else:
                leaf = jnp.full(shape, 1.0 if kind == "ones" else 0.0,
                                jnp.float32)
            leaves.append(leaf)
        return leaves

    tree: dict = {}
    for path, leaf in zip(paths, draw(jax.random.key(seed))):
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    return tree
