"""Decompose the bench round's device time: train+aggregate vs eval.

Times the jitted round program and the jitted eval program separately by
chaining N dispatches and fetching one scalar at the end (a per-step
fetch would serialize dispatch with execution; see docs/PERFORMANCE.md
"Profiling method").

Usage: python scripts/profile_round.py [model] [chunk] [dtype] [evalbatch]
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp


def main():
    model_name = sys.argv[1] if len(sys.argv) > 1 else "resnet18"
    chunk = int(sys.argv[2]) if len(sys.argv) > 2 else 50
    dtype = sys.argv[3] if len(sys.argv) > 3 else "float32"
    eval_batch = int(sys.argv[4]) if len(sys.argv) > 4 else 10000

    from distributed_learning_simulator_tpu.config import ExperimentConfig
    from distributed_learning_simulator_tpu.data.registry import get_dataset
    from distributed_learning_simulator_tpu.factory import get_algorithm
    from distributed_learning_simulator_tpu.models.registry import (
        get_model,
        init_params,
    )
    from distributed_learning_simulator_tpu.parallel.engine import (
        make_decoder,
        make_eval_fn,
        make_optimizer,
        make_reshaper,
        pad_eval_set,
    )
    from distributed_learning_simulator_tpu.simulator import build_client_data

    config = ExperimentConfig(
        dataset_name="cifar10", model_name=model_name,
        distributed_algorithm="fed", worker_number=1000, round=3, epoch=1,
        learning_rate=0.1, momentum=0.9, batch_size=25, log_level="WARNING",
        eval_batch_size=eval_batch, client_chunk_size=chunk,
        local_compute_dtype=dtype,
    )
    dataset = get_dataset(config.dataset_name, seed=0)
    client_data = build_client_data(config, dataset)
    eval_batches = tuple(
        jnp.asarray(a) for a in pad_eval_set(
            dataset.x_test, dataset.y_test, config.eval_batch_size,
            flatten=True,
        )
    )
    model = get_model(config.model_name, num_classes=dataset.num_classes)
    params = init_params(model, dataset.x_train[:1], seed=0)
    optimizer = make_optimizer("SGD", 0.1, momentum=0.9)
    algorithm = get_algorithm("fed", config)
    reshaper = make_reshaper(dataset.x_test.shape[1:])
    evaluate = jax.jit(make_eval_fn(model.apply, preprocess=reshaper))
    algorithm.prepare(model.apply, make_eval_fn(model.apply,
                                                preprocess=reshaper))
    round_fn = algorithm.make_round_fn(
        model.apply, optimizer, client_data.n_clients,
        preprocess=make_decoder(client_data.sample_shape),
    )
    round_jit = jax.jit(round_fn)

    cx = jnp.asarray(client_data.x)
    cy = jnp.asarray(client_data.y)
    cmask = jnp.asarray(client_data.mask)
    sizes = jnp.asarray(client_data.sizes)
    key = jax.random.key(1)

    def time_rounds(n):
        g = params
        t0 = time.perf_counter()
        for i in range(n):
            g, _, aux = round_jit(g, None, cx, cy, cmask, sizes,
                                  jax.random.fold_in(key, i))
        jax.device_get(aux["mean_client_loss"])
        return (time.perf_counter() - t0) / n

    def time_eval(n):
        t0 = time.perf_counter()
        for _ in range(n):
            m = evaluate(params, *eval_batches)
        jax.device_get(m["accuracy"])
        return (time.perf_counter() - t0) / n

    time_rounds(1)  # compile
    time_eval(1)
    tr = time_rounds(5)
    te = time_eval(5)
    print(f"model={model_name} chunk={chunk} dtype={dtype} "
          f"eval_batch={eval_batch}")
    print(f"train+aggregate: {tr*1000:.0f} ms/round")
    print(f"eval:            {te*1000:.0f} ms/round")
    print(f"sum:             {(tr+te)*1000:.0f} ms/round "
          f"(target < 3000 ms for 333.3 c·r/s)")


if __name__ == "__main__":
    main()
