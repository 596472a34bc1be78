"""Task: next-token prediction over integer sequences, one target a position.

``data_spec`` (the configuration's ``"data"``): ``shape`` ``[T]`` (positions
a sample), ``vocab`` (ids are drawn below it: for a model that holds a
slice of its vocabulary, the slice), ``n_train``, ``n_test``.

The data is a seeded low-order Markov source (there is no network):
every id has two likely successors, drawn once from the seed; a position
follows one of them with probability 0.9 and is uniform over the
vocabulary otherwise. So there is something to learn, from the scale of
the logits down to the successor table, and the loss moves as training's
does. A sample is ``T + 1`` ids: the first ``T`` are the inputs, the last
``T`` the targets (the next id at every position); every position is real
(mask all ones).
"""

from __future__ import annotations

import numpy as np

FOLLOW = 0.9


def make(seed: int, data_spec: dict) -> dict:
    """``x_*`` int32 ``[n, T]`` inputs, ``y_*`` int32 ``[n, T]`` targets."""
    (length,), vocab = data_spec["shape"], data_spec["vocab"]
    successors = np.random.default_rng(seed).integers(
        0, vocab, size=(vocab, 2), dtype=np.int32
    )

    def split(n, sub_seed):
        rng = np.random.default_rng(sub_seed)
        ids = np.empty((n, length + 1), np.int32)
        ids[:, 0] = rng.integers(0, vocab, n)
        which = rng.integers(0, 2, (n, length))
        other = rng.integers(0, vocab, (n, length), dtype=np.int32)
        follows = rng.random((n, length)) < FOLLOW
        for t in range(length):
            ids[:, t + 1] = np.where(
                follows[:, t], successors[ids[:, t], which[:, t]],
                other[:, t],
            )
        return ids[:, :-1].copy(), ids[:, 1:].copy()

    x_train, y_train = split(data_spec["n_train"], seed + 1)
    x_test, y_test = split(data_spec["n_test"], seed + 2)
    return {"x_train": x_train, "y_train": y_train,
            "x_test": x_test, "y_test": y_test}


def program_dataset(name: str, data: dict, data_spec: dict):
    """What ``run_simulation(config, dataset=...)`` is handed: the ids as
    they are, the vocabulary as the number of classes."""
    from distributed_learning_simulator_tpu.data.registry import Dataset

    return Dataset(name, data["x_train"], data["y_train"],
                   data["x_test"], data["y_test"], data_spec["vocab"])


def clients(data: dict, n_clients: int, seed: int) -> dict:
    """Equal IID shards, one per client: sample ``perm[i*s:(i+1)*s]`` of a
    seeded permutation goes to client ``i`` (the program's ``iid``
    partition draws the same one)."""
    perm = np.random.default_rng(seed).permutation(len(data["x_train"]))
    shard = len(perm) // n_clients
    idx = perm[: shard * n_clients].reshape(n_clients, shard)
    y = data["y_train"][idx]
    return {
        "x": data["x_train"][idx], "y": y,
        "mask": np.ones(y.shape, np.float32),  # [clients, shard, T]
        "sizes": np.full((n_clients,), float(shard), np.float32),
        "x_test": data["x_test"], "y_test": data["y_test"],
    }


def decode(stored, data_spec: dict):
    return stored


def loss(outputs, targets, mask=None):
    """``(sum of the real targets' losses, count of real targets)``:
    softmax cross-entropy of ``outputs`` ``[n, T, vocab]`` against the
    next id at every position; ``mask`` ``[n, T]`` (``None``: all real)."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(outputs)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is None:
        return jnp.sum(nll), nll.size
    return jnp.sum(nll * mask), jnp.sum(mask)


def work_per_sample(data_spec: dict) -> int:
    """Units of work in a sample: its positions (tokens)."""
    return int(data_spec["shape"][0])
