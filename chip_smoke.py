#!/usr/bin/env python3
"""Chip smoke: the flagship federated round, once, on the accelerator.

Drives the normal entry path — ``config.get_config(argv)`` ->
``simulator.run_simulation`` -> ``parallel/engine.py`` ->
``algorithms/fedavg.py`` — at the full width of the flagship the repo
already defines (examples/resnet18_podrate_1chip.sh): 1000 clients x
ResNet-18 x ``fed`` on CIFAR-10-shaped data, batch 25, one local epoch,
momentum 0.9, ``client_chunk_size=40``, bf16 local state, one 10000-sample
eval batch. Only the number of rounds is cut (round 0 carries the
compile). Two rounds each of ``sign_SGD`` and ``fed_quant`` follow on the
same model in the same process, and on a host with >= 4 chips the ``fed``
leg also runs first with ``--mesh_devices 4``.

Each leg must: run every round; report finite ``test_loss`` with the last
below the first; compile nothing after round 0; return finite params; and
(on an accelerator) report its peak device memory: ``peak_bytes_in_use``
(live arrays) and ``peak_bytes_reserved`` (program temporaries). Round
times are the simulator's own ``round_seconds`` — host clock between
successive metric fetches — printed as set-up (round 0 minus a steady
round) apart from the steady round. They are smoke readings, not a
benchmark.

One process owns the chip: this one. Nothing here touches JAX before
``main`` and no child process is started. Refuses to run unless
``jax.devices()[0].platform == "tpu"``. The last line of stdout is the
verdict, ``{"ok": true, "device": {...}}``; exit code 0 only when every
check of every leg passed.
"""

from __future__ import annotations

import json
import math
import os
import statistics
import sys
import time

# The flagship as the CLI would be given it. lr 0.02 is the rate at which
# this GroupNorm ResNet learns from scratch at two steps per round
# (examples/resnet18_converge_1chip.sh); the loss must fall within the
# smoke's few rounds. Round times do not depend on it.
FLAGSHIP_ARGV = [
    "--dataset_name", "cifar10", "--model_name", "resnet18",
    "--worker_number", "1000", "--epoch", "1", "--batch_size", "25",
    "--client_chunk_size", "40", "--eval_batch_size", "10000",
    "--telemetry_level", "basic", "--log_level", "INFO",
    "--log_root", os.path.join("chiprun_out", "chip_smoke_log"),
]
# Per-algorithm operating points of the flagship family
# (docs/PERFORMANCE.md § Round 5): sign_SGD runs momentum 0 in f32.
LEG_ARGV = {
    "fed": [
        "--distributed_algorithm", "fed", "--round", "4",
        "--learning_rate", "0.02", "--momentum", "0.9",
        "--local_compute_dtype", "bfloat16",
    ],
    "sign_SGD": [
        "--distributed_algorithm", "sign_SGD", "--round", "2",
        "--learning_rate", "0.01", "--momentum", "0.0",
    ],
    "fed_quant": [
        "--distributed_algorithm", "fed_quant", "--round", "2",
        "--learning_rate", "0.02", "--momentum", "0.9",
        "--local_compute_dtype", "bfloat16",
    ],
}
MESH_CHIPS = 4
# Sharding the client axis changes the order of the f32 reductions, not
# the math. Round 0 starts from the same params, so its test_loss must
# agree with the one-chip run closely; after that the bf16 stochastic
# rounding of the local state amplifies the round-off (v5e readings,
# PR 21: 5e-6 at round 0, 5e-3 by round 3).
MESH_LOSS_RTOL_ROUND_0 = 1e-3
MESH_LOSS_RTOL = 2e-2
# Each chip trains its share of every chunk, so peaks should be near
# equal; one chip carrying the others' work would break this.
MESH_PEAK_SPREAD = 1.25


def require_tpu() -> dict:
    """The device as JAX reports it; exits non-zero unless it is a TPU."""
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu":
        sys.exit(
            "chip_smoke: needs a TPU; JAX found platform "
            f"{device['platform']!r} ({device['kind']} x{device['count']})"
        )
    print(
        f"chip_smoke: platform={device['platform']} "
        f"device_kind={device['kind']} device_count={device['count']}",
        flush=True,
    )
    return device


def run_leg(name: str, config) -> dict:
    """One ``run_simulation`` of ``config`` and the checks on what came
    out. ``leg["failed"]`` lists the checks that did not hold."""
    import jax
    import jax.numpy as jnp

    from distributed_learning_simulator_tpu.simulator import run_simulation
    from distributed_learning_simulator_tpu.telemetry import (
        device_memory_stats,
        peak_hbm_bytes,
    )

    t0 = time.perf_counter()
    result = run_simulation(config)
    wall = time.perf_counter() - t0
    history = result["history"]
    losses = [h["test_loss"] for h in history]
    seconds = [h["round_seconds"] for h in history]
    steady = statistics.median(seconds[1:])  # a leg has >= 2 rounds
    leaves = jax.tree_util.tree_leaves(result["global_params"])
    mesh = result["mesh"]
    devices = (
        list(mesh.devices.flat) if mesh is not None
        else [jax.local_devices()[0]]
    )
    peaks = [peak_hbm_bytes(d) for d in devices]
    # On this backend peak_bytes_in_use is the high-water mark of live
    # arrays only; the executables' temporaries (the training transients
    # of the clients in flight) are counted in peak_bytes_reserved.
    reserved = [
        (device_memory_stats(d) or {}).get("peak_bytes_reserved")
        for d in devices
    ]
    checks = {
        "every_round_ran": len(history) == config.round,
        "test_loss_finite": all(math.isfinite(v) for v in losses),
        "test_loss_fell": losses[-1] < losses[0],
        # None (telemetry off) is a failure too: the count must be taken.
        "no_compile_after_round_0": result["post_warmup_compiles"] == 0,
        "params_finite": all(
            bool(jnp.all(jnp.isfinite(leaf))) for leaf in leaves
        ),
        # The CPU backend reports no memory statistics; an accelerator
        # that reports none fails.
        "peak_memory_reported": (
            devices[0].platform == "cpu" or None not in peaks
        ),
    }
    if mesh is not None:
        checks["mesh_devices_distinct"] = (
            len({d.id for d in devices}) == config.mesh_devices
        )
        if None not in peaks:
            totals = [p + (r or 0) for p, r in zip(peaks, reserved)]
            checks["per_chip_peak_about_equal"] = (
                max(totals) <= MESH_PEAK_SPREAD * min(totals)
            )
    leg = {
        "leg": name,
        "algorithm": config.distributed_algorithm,
        "model": config.model_name,
        "clients": config.worker_number,
        "client_chunk_size": result["client_chunk_size"],
        "mesh_devices": [d.id for d in devices],
        "params": sum(leaf.size for leaf in leaves),
        "test_loss": losses,
        "test_accuracy": [h["test_accuracy"] for h in history],
        "round_seconds": [round(s, 3) for s in seconds],
        "setup_seconds": round(seconds[0] - steady, 3),
        "steady_round_seconds": round(steady, 4),
        "leg_wall_seconds": round(wall, 2),
        "compiles_round_0": history[0].get("telemetry", {}).get("compiles"),
        "compiles_after_round_0": result["post_warmup_compiles"],
        "peak_bytes_in_use": peaks,
        "peak_bytes_reserved": reserved,
        "failed": [k for k, ok in checks.items() if not ok],
    }
    print(f"[{name}] " + json.dumps(leg), flush=True)
    return leg


def main() -> int:
    # The program first (importing it starts no backend): without it there
    # is nothing to smoke, and nothing is printed.
    from distributed_learning_simulator_tpu.config import get_config
    from distributed_learning_simulator_tpu.data.registry import dataset_file

    device = require_tpu()
    import jax

    npz = dataset_file("cifar10")
    print(
        "chip_smoke: data = "
        + (npz if os.path.exists(npz) else
           f"the seeded synthetic CIFAR-10 surrogate (no {npz}); same "
           "shapes and work, not real pixels"),
        flush=True,
    )

    def leg_config(name, *extra):
        return get_config(FLAGSHIP_ARGV + LEG_ARGV[name] + list(extra))

    legs = []
    mesh_leg = None
    if device["count"] >= MESH_CHIPS:
        # First in the process: peak_bytes_in_use is cumulative, so the
        # per-chip comparison needs chips no earlier leg has used.
        mesh_leg = run_leg(
            f"fed_mesh{MESH_CHIPS}",
            leg_config("fed", "--mesh_devices", str(MESH_CHIPS)),
        )
        legs.append(mesh_leg)
    else:
        print(
            f"chip_smoke: {MESH_CHIPS}-chip leg skipped: "
            f"{device['count']} device(s) visible",
            flush=True,
        )
    for name in LEG_ARGV:
        legs.append(run_leg(name, leg_config(name)))
    print(
        "chip_smoke: compile cache = "
        f"{jax.config.jax_compilation_cache_dir} "
        + ("(JAX_COMPILATION_CACHE_DIR)"
           if os.environ.get("JAX_COMPILATION_CACHE_DIR")
           else "(set by utils/compile_cache.py)"),
        flush=True,
    )
    if mesh_leg is not None:
        one_chip = next(leg for leg in legs if leg["leg"] == "fed")
        pairs = list(zip(mesh_leg["test_loss"], one_chip["test_loss"]))
        agree = len(pairs) == len(one_chip["test_loss"]) and all(
            math.isclose(
                a, b,
                rel_tol=MESH_LOSS_RTOL if i else MESH_LOSS_RTOL_ROUND_0,
            )
            for i, (a, b) in enumerate(pairs)
        )
        if not agree:
            mesh_leg["failed"].append("test_loss_matches_one_chip")

    def gib(values):
        return ", ".join(
            "not reported" if v is None else f"{v / 2**30:.2f} GiB"
            for v in values
        )

    for leg in legs:
        print(
            f"chip_smoke: {leg['leg']:>10}: "
            f"test_loss {' -> '.join(f'{v:.4f}' for v in leg['test_loss'])}; "
            f"set-up {leg['setup_seconds']} s, steady round "
            f"{leg['steady_round_seconds']} s; compiles after round 0: "
            f"{leg['compiles_after_round_0']}; peak_bytes_in_use "
            f"{gib(leg['peak_bytes_in_use'])} + peak_bytes_reserved "
            f"{gib(leg['peak_bytes_reserved'])}; "
            + ("FAILED " + ",".join(leg["failed"]) if leg["failed"]
               else "ok")
        )
    failed = {leg["leg"]: leg["failed"] for leg in legs if leg["failed"]}
    verdict = {"ok": not failed, "device": device}
    if failed:
        verdict["failed"] = failed
    print(json.dumps(verdict), flush=True)
    return 0 if not failed else 1


if __name__ == "__main__":
    sys.exit(main())
