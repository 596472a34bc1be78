"""Device-trace profile of one round at ResNet scale (any algorithm).

Round-3 method (docs/PERFORMANCE.md): jax.profiler's device lane
events in vm.trace.json.gz carry per-op ``dur``
and ``raw_bytes_accessed``, which is the only reliable attribution of
round time (isolated microbenches lie — measured round 3).

Usage: python scripts/profile_sign_round.py [chunk] [trace_dir] [algo] [dtype]
(algo default sign_SGD; dtype default float32 — use bfloat16 for the fed
flagship configuration.)
"""

from __future__ import annotations

import os
import sys
import time
from collections import defaultdict

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp


def build_round(chunk: int, algo: str = "sign_SGD", dtype: str = "float32"):
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
    )
    from distributed_learning_simulator_tpu.simulator import build_client_data

    momentum = 0.0 if algo == "sign_SGD" else 0.9
    config = ExperimentConfig(
        dataset_name="cifar10", model_name="resnet18",
        distributed_algorithm=algo, worker_number=1000, round=3,
        epoch=1, learning_rate=0.01, momentum=momentum, batch_size=25,
        log_level="WARNING", client_chunk_size=chunk,
        local_compute_dtype=dtype,
    )
    dataset = get_dataset(config.dataset_name, seed=0)
    client_data = build_client_data(config, dataset)
    model = get_model(config.model_name, num_classes=dataset.num_classes)
    params = init_params(model, dataset.x_train[:1], seed=0)
    optimizer = make_optimizer("SGD", config.learning_rate,
                               momentum=momentum)
    algorithm = get_algorithm(algo, config)
    algorithm.prepare(model.apply, make_eval_fn(model.apply))
    round_fn = algorithm.make_round_fn(
        model.apply, optimizer, client_data.n_clients,
        preprocess=make_decoder(client_data.sample_shape),
        client_sizes=client_data.sizes,
    )
    round_jit = jax.jit(round_fn)
    operands = (
        params, None, jnp.asarray(client_data.x),
        jnp.asarray(client_data.y), jnp.asarray(client_data.mask),
        jnp.asarray(client_data.sizes),
    )
    return round_jit, operands


def parse_trace(trace_dir: str, top: int = 30):
    from distributed_learning_simulator_tpu.utils.tracing import (
        iter_device_ops,
    )

    # Group by (hlo op family, shape prefix): instance ids collapse so the
    # per-(op, shape) totals attribute round time by program structure.
    by_op: dict[tuple, list[float]] = defaultdict(lambda: [0.0, 0.0, 0])
    total = 0.0
    for ev in iter_device_ops(trace_dir):
        args = ev.get("args") or {}
        dur = float(ev.get("dur", 0.0))  # us
        fam = ev.get("name", "?").split(".")[0]
        key = (fam, args.get("long_name", "")[:90])
        rec = by_op[key]
        rec[0] += dur
        rec[1] += float(args.get("raw_bytes_accessed", 0) or 0)
        rec[2] += 1
        total += dur
    rows = sorted(by_op.items(), key=lambda kv: -kv[1][0])[:top]
    print(f"total device op time: {total / 1e3:.1f} ms")
    print(f"{'op':82s} {'ms':>9s} {'GB':>8s} {'GB/s':>7s} {'n':>6s}")
    for (fam, ln), (dur, byt, cnt) in rows:
        gbps = (byt / 2**30) / (dur / 1e6) if dur else 0.0
        label = f"{fam} {ln}"[:82]
        print(f"{label:82s} {dur / 1e3:9.1f} {byt / 2**30:8.2f} "
              f"{gbps:7.0f} {cnt:6d}")
    return total


def main():
    chunk = int(sys.argv[1]) if len(sys.argv) > 1 else 40
    trace_dir = sys.argv[2] if len(sys.argv) > 2 else "/tmp/sign_trace"
    algo = sys.argv[3] if len(sys.argv) > 3 else "sign_SGD"
    dtype = sys.argv[4] if len(sys.argv) > 4 else "float32"
    round_jit, operands = build_round(chunk, algo, dtype)
    key = jax.random.key(1)

    t0 = time.perf_counter()
    g, st, aux = round_jit(*operands, jax.random.fold_in(key, 0))
    jax.device_get(aux["mean_client_loss"])
    print(f"compile+first round: {time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    for i in range(1, 4):
        g, st, aux = round_jit(
            operands[0], st, *operands[2:], jax.random.fold_in(key, i)
        )
    jax.device_get(aux["mean_client_loss"])
    per_round = (time.perf_counter() - t0) / 3
    print(f"steady state: {per_round * 1000:.0f} ms/round "
          f"({1000 / per_round:.0f} c*r/s)")

    jax.profiler.start_trace(trace_dir)
    g, st, aux = round_jit(
        operands[0], st, *operands[2:], jax.random.fold_in(key, 9)
    )
    jax.device_get(aux["mean_client_loss"])
    jax.profiler.stop_trace()
    parse_trace(trace_dir)


if __name__ == "__main__":
    main()
