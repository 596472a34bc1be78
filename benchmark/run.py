#!/usr/bin/env python3
"""One run of one benchmark cell: ``python benchmark/run.py --workload
<name> --seed <n> --seconds <s> --trace <0|1>``.

One process, which owns the chip(s) and starts no child. It makes the
data and the starting weights from ``--seed``, builds the program's
config as its CLI would (``config.get_config(argv)``) and makes ONE
``simulator.run_simulation`` call. That call first runs the rounds the
comparison follows (they compile and warm up: set-up), writes its own
checkpoint at the last of them, and goes on into the measured window,
which this script closes after ``--seconds`` with the program's graceful
stop. The window's rounds and their times are the program's own
per-round records. Once the call has returned and the peak memory is
read, the plain reference follows the same first rounds from the same
weights and data, and ``correct`` is the comparison of the two
(``harness/compare.py``; limits in ``workloads/<cell>.json``).

The last line of standard output is the result's JSON object.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import signal
import sys
import tempfile
import time

T_PROCESS = time.perf_counter()

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (BENCH_DIR, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import compare, data as bench_data, spec as bench_spec  # noqa: E402
from harness import trace as bench_trace  # noqa: E402
from harness.window import Window  # noqa: E402

# The followed rounds end at round index CKPT_EVERY - 1 of a run that
# "resumes" CKPT_EVERY - rounds in: the program's periodic checkpoint then
# fires exactly once, at the last followed round, and never again inside
# any window (the next is CKPT_EVERY rounds on).
CKPT_EVERY = 1_000_000
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, found by the name a data file gives."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_peaks() -> dict:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        return json.load(f)


def require_devices(chips: int) -> dict:
    """The device as JAX reports it; exits non-zero, printing no result,
    unless it is a TPU with the chips the cell asks for."""
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu" or device["count"] < chips:
        sys.exit(
            f"benchmark: cell needs {chips} TPU chip(s); JAX found "
            f"{device['platform']!r} ({device['kind']} x{device['count']})"
        )
    return device


def place_compile_cache() -> str:
    """Where the program puts JAX's persistent cache, applied before the
    benchmark's own first jit so that everything shares one directory:
    ``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``
    (the program's default, ``utils/compile_cache.py``)."""
    import jax

    # Every program goes into the cache, however quickly it compiled: the
    # program initialises its model op by op, hundreds of sub-second
    # compiles that JAX's default threshold (1 s) would redo in every run.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileLog:
    """Every backend compile (or cache load) with when it ended: the
    benchmark's own count, read through ``jax.monitoring``."""

    def __init__(self):
        self.events: list[tuple[float, float]] = []

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs):
        if event == _COMPILE_EVENT:
            self.events.append((time.perf_counter(), duration))


def memory_peaks(devices) -> list[dict]:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append({
            "in_use": int(stats.get("peak_bytes_in_use", 0)),
            "reserved": int(stats.get("peak_bytes_reserved", 0)),
        })
    return out


def run_program(cell: dict, seed: int, seconds: float, trace: bool,
                work_dir: str) -> dict:
    """Set-up, the one ``run_simulation`` call with its window, and what
    it left: history, the checkpoint of the last followed round, memory
    peaks, compile log and (traced) the trace's reduction."""
    import jax
    import numpy as np

    from distributed_learning_simulator_tpu.config import get_config
    from distributed_learning_simulator_tpu.data.registry import Dataset
    from distributed_learning_simulator_tpu.simulator import run_simulation
    from distributed_learning_simulator_tpu.utils.checkpoint import (
        load_checkpoint,
        save_checkpoint,
    )

    config_file, pairing = cell["config"], cell["pairing"]
    rounds = pairing["compare_rounds"]
    ckpt_dir = os.path.join(work_dir, "ckpt")
    fixed_round = CKPT_EVERY - 1
    argv = bench_spec.program_argv(cell, seed, [
        "--round", str(2 * CKPT_EVERY),
        "--checkpoint_dir", ckpt_dir,
        "--checkpoint_every", str(CKPT_EVERY),
        "--resume", "true",
        "--log_root", os.path.join(work_dir, "log"),
        "--telemetry_level", "basic",
    ])
    config = get_config(argv)

    d = config_file["data"]
    data = bench_data.make_data(
        seed, d["shape"], d["classes"], d["n_train"], d["n_test"]
    )

    def images(u8):
        return (u8.astype(np.float32) / np.float32(255.0)).reshape(
            (u8.shape[0],) + tuple(d["shape"])
        )

    dataset = Dataset(
        config.dataset_name, images(data["x_train"]), data["y_train"],
        images(data["x_test"]), data["y_test"], d["classes"],
    )
    reference = load_module("references", config_file["reference"])
    layout = reference.layout(config_file["model"], d["shape"])
    params0 = bench_data.init_params(layout, seed)
    save_checkpoint(
        os.path.join(ckpt_dir, f"start_{fixed_round - rounds}.ckpt"),
        fixed_round - rounds, params0, None,
    )
    params0 = jax.tree_util.tree_map(np.asarray, params0)

    ckpt_path = os.path.join(ckpt_dir, f"round_{fixed_round}.ckpt")
    trace_dir = os.path.join(work_dir, "trace")

    def start_trace():
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)

    # A traced run captures the last ``trace_seconds`` of its window; the
    # capture ends, and is written out, only once the stop has been sent,
    # so writing it lengthens no window.
    tail = (
        (pairing["trace_seconds"], start_trace, jax.profiler.stop_trace)
        if trace else None
    )

    # A SIGTERM that lands outside the program's own handler (before it
    # is installed, after it is restored) must not kill the run.
    previous = signal.signal(signal.SIGTERM, lambda *a: None)
    try:
        with CompileLog() as compiles, Window(
            ckpt_path, seconds, tail=tail
        ) as window:
            result = run_simulation(config, dataset=dataset)
            t_return = time.perf_counter()
    finally:
        signal.signal(signal.SIGTERM, previous)
    if window.error is not None:
        raise RuntimeError("the window's watcher failed") from window.error
    if not window.signalled:
        raise RuntimeError(
            "run_simulation returned before the window was closed"
        )
    history = result["history"]
    mesh = result["mesh"]
    devices = (
        list(mesh.devices.flat) if mesh is not None
        else [jax.local_devices()[0]]
    )
    out = {
        "config": config,
        "data": data,
        "params0": params0,
        "set_up_rows": history[:rounds],
        "window_rows": history[rounds:],
        "checkpoint": load_checkpoint(ckpt_path),
        "opened_at": window.opened_at,
        "returned_at": t_return,
        "compile_events": compiles.events,
        "post_warmup_compiles": result["post_warmup_compiles"],
        "memory": memory_peaks(devices),
        "trace": None,
    }
    if [r["round"] for r in history[:rounds]] != list(
        range(fixed_round - rounds + 1, fixed_round + 1)
    ):
        raise RuntimeError(
            "the program did not start at the round the benchmark set: "
            f"{[r['round'] for r in history[:rounds + 1]]}"
        )
    del result, dataset
    gc.collect()
    if trace:
        out["trace"] = bench_trace.reduce(bench_trace.load(trace_dir))
    return out


def reference_spec(cell: dict, run: dict) -> dict:
    """What the plain reference needs to know of the experiment."""
    config, pairing = run["config"], cell["pairing"]
    return {
        "seed": config.seed,
        "rounds": pairing["compare_rounds"],
        "learning_rate": config.learning_rate,
        "momentum": config.momentum,
        "batch_size": config.batch_size,
        "epochs": config.epoch,
        "sample_shape": tuple(cell["config"]["data"]["shape"]),
        "block_clients": pairing["reference_block_clients"],
        "eval_block": pairing["reference_eval_block"],
    }


def follow_reference(cell: dict, run: dict, **precisions):
    """The plain reference over the followed rounds, from the same
    weights and data. Returns its raw result (``references/<algo>.py``);
    ``precisions`` are a control's (``precision``, ``state_precision``)."""
    config_file = cell["config"]
    config = run["config"]
    model_ref = load_module("references", config_file["reference"])
    algo_ref = load_module("references", cell["traffic"]["comparator"])
    clients = bench_data.iid_clients(
        run["data"], config.worker_number, config.seed
    )
    return algo_ref.run(
        model_ref.forward, config_file["model"], run["params0"], clients,
        reference_spec(cell, run), **precisions,
    )


def program_record(run: dict) -> dict:
    rows = run["set_up_rows"]
    return compare.record(
        [r["test_loss"] for r in rows],
        [r["mean_client_loss"] for r in rows],
        run["checkpoint"]["global_params"], run["params0"],
    )


def reference_record(ref_run: dict, params0) -> dict:
    return compare.record(
        ref_run["test_loss"], ref_run["client_loss"], ref_run["params"],
        params0, groups=(ref_run["group_sums"], ref_run["group_weights"],
                         ref_run["subsets"]),
    )


def read_metrics(cell: dict, run: dict, device: dict, entries) -> dict:
    """Every metric, end-to-end or per-layer, is read by a file of its
    own, ``metrics/<name>.py``, from this one context; a reader that finds
    nothing to read returns ``None`` and its metric is left out."""
    config_file = cell["config"]
    peaks = load_peaks()
    if device["kind"] not in peaks:
        raise SystemExit(
            f"device kind {device['kind']!r} is not in benchmark/peaks.json"
        )
    flops = load_module("flops", config_file["flops"])
    config = run["config"]
    shard = config_file["data"]["n_train"] // config.worker_number
    ctx = {
        "setup_s": run["opened_at"] - T_PROCESS,
        "window_seconds": [r["round_seconds"] for r in run["window_rows"]],
        "clients": config.worker_number,
        "train_flops_per_round": (
            config.worker_number * shard * config.epoch
            * flops.train_flops_per_sample(
                config_file["model"], config_file["data"]["shape"]
            )
        ),
        "chips": cell["cell"]["chips"],
        "peaks": peaks[device["kind"]],
        "compile_events": run["compile_events"],
        "opened_at": run["opened_at"],
        "post_warmup_compiles": run["post_warmup_compiles"],
        "memory": run["memory"],
        "trace": run["trace"],
    }
    values = {}
    for entry in entries:
        value = load_module("metrics", entry["name"]).read(ctx)
        if value is not None:
            values[entry["name"]] = value
    return values


def main(argv=None, root: str = ROOT) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    cell = bench_spec.load_cell(root, args.workload)
    try:
        import distributed_learning_simulator_tpu  # noqa: F401
    except ImportError as e:
        sys.exit(f"benchmark: the program is not in this checkout ({e})")
    device = require_devices(cell["cell"]["chips"])
    place_compile_cache()

    scratch = os.path.join(ROOT, ".bench_work")
    os.makedirs(scratch, exist_ok=True)
    work_dir = tempfile.mkdtemp(prefix="run_", dir=scratch)
    try:
        run = run_program(
            cell, args.seed, args.seconds, bool(args.trace), work_dir
        )
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)

    entries = bench_spec.metrics_for(
        cell["bench"], "per_layer" if args.trace else "end_to_end",
        args.workload,
    )
    values = read_metrics(cell, run, device, entries)
    units = {e["name"]: e["unit"] for e in entries}
    metrics = {
        name: {"value": values[name], "unit": units[name]}
        for name in units if name in values
    }
    device["memory_peak_bytes"] = max(
        m["in_use"] + m["reserved"] for m in run["memory"]
    )
    result = {
        "attempted": len(run["window_rows"]),
        "failed": sum(
            1 for r in run["window_rows"] if r.get("round_rejected")
        ),
        "metrics": metrics,
        "device": device,
    }
    if args.trace and run["trace"] is not None:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = {
            "device_ops": run["trace"]["device_ops"],
            "idle_gaps": run["trace"]["idle_gaps"],
        }

    # The reference runs last: after the peak memory is read and the
    # program's state is freed, and outside set-up and window alike.
    t_ref = time.perf_counter()
    ref_run = follow_reference(cell, run)
    nums = compare.numbers(
        program_record(run), reference_record(ref_run, run["params0"])
    )
    ok, table = compare.judge(nums, cell["pairing"]["limits"])
    result["reference_s"] = time.perf_counter() - t_ref
    result["observed"] = {
        name: nums[name] for name in sorted(set(nums) - set(table))
    }
    result["compared"] = {
        name: [row["value"], row["limit"]] for name, row in table.items()
    }
    result = {"correct": ok, **result}
    for name in sorted(set(nums) - set(table)):
        print(f"observed {name}: {nums[name]:.6g} (no limit)", file=sys.stderr)
    for name, row in table.items():
        print(f"compared {name}: {row['value']:.6g} (limit {row['limit']:g})",
              file=sys.stderr)
    print(f"correct: {ok}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
