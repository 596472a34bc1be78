#!/usr/bin/env python3
"""``calibrate.py`` for a cell whose comparator is ``fed_one_in_flight``:
the readings its limits are set from, on the chip, in one process.

``python benchmark/calibrate_one_in_flight.py --workload <cell> --seeds
1,2,... [--controls 3] [--faults 3] [--products 0] [--program 1]
[--seconds 2]``. The same readings as ``calibrate.py`` (which may not be
edited and plants its faults through ``compare.record``'s named subsets: a
float64 copy of every parameter for each, more than a one-chip machine's
host memory holds at 841 M parameters). For every seed: the cell's own run
against the plain reference (the lower readings; ``--program 0`` leaves
the program out and reads only what has to fail, which is the reference
against itself). What has to fail is put in the program's place through
the same ``compare.numbers``: for the first ``--controls`` seeds the
reference in the next precision below the one the configuration states
(products, activations and the clients' stored state alike; with
``--products 1`` also the products alone) and a state handed back
unchanged (its numbers written down from their definitions: no parameter
moves); for the first ``--faults`` seeds the reference's last mean taken
over the first half of the clients alone and over the odd clients alone
(from the comparator's two subset sums, one fault at a time).
Every record is freed before the next is built. One JSON line per seed on
standard output and in ``chiprun_out/``. Not run by the benchmark's own
runs.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

import run as bench_run
from harness import compare, spec as bench_spec

def follow(cell: dict, run: dict, **kwargs):
    """``run.follow_reference`` with the comparator's own keywords."""
    config_file, config = cell["config"], run["config"]
    model_ref = bench_run.load_module("references", config_file["reference"])
    algo_ref = bench_run.load_module(
        "references", cell["traffic"]["comparator"]
    )
    spec = bench_run.reference_spec(cell, run)
    clients = spec["task"].clients(
        run["data"], config.worker_number, config.seed
    )
    return algo_ref.run(
        model_ref.forward, config_file["model"], run["params0"], clients,
        spec, **kwargs,
    )


def inputs(cell: dict, seed: int) -> dict:
    """What ``run.run_program`` makes from the seed before it calls the
    program: the configuration, the data and the starting weights."""
    import jax

    from distributed_learning_simulator_tpu.config import get_config
    from harness import data as bench_data

    config_file = cell["config"]
    d = config_file["data"]
    reference = bench_run.load_module("references", config_file["reference"])
    params0 = bench_data.init_params(
        reference.layout(config_file["model"], d["shape"]), seed
    )
    return {
        "config": get_config(bench_spec.program_argv(cell, seed, [])),
        "data": bench_run.load_task(config_file).make(seed, d),
        "params0": jax.tree_util.tree_map(np.asarray, params0),
    }


def fault_mean(ref_run: dict, name: str):
    """The last round's mean over the named subset alone, as a parameter
    tree; the subset's sum is consumed."""
    import jax

    total = ref_run["subset_sums"].pop(name)
    weight = np.float32(ref_run["subset_weights"][name])
    for leaf in jax.tree_util.tree_leaves(total):
        leaf /= weight
    return total


def main(argv=None, root: str = bench_run.ROOT,
         out_dir: str = os.path.join(bench_run.ROOT, "chiprun_out")) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--controls", type=int, default=3)
    parser.add_argument("--products", type=int, choices=(0, 1), default=0)
    parser.add_argument("--faults", type=int, default=3)
    parser.add_argument("--program", type=int, choices=(0, 1), default=1)
    parser.add_argument("--seconds", type=float, default=2.0)
    args = parser.parse_args(argv)
    cell = bench_spec.load_cell(root, args.workload)
    bench_run.require_devices(cell["cell"]["chips"])
    bench_run.place_compile_cache()
    config_file = cell["config"]
    model_ref = bench_run.load_module("references", config_file["reference"])
    algo_ref = bench_run.load_module(
        "references", cell["traffic"]["comparator"]
    )
    lower = algo_ref.NEXT_LOWER[config_file["matmul_dtype"]]
    os.makedirs(out_dir, exist_ok=True)
    scratch = os.path.join(bench_run.ROOT, ".bench_work")
    os.makedirs(scratch, exist_ok=True)
    lines = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        planted = i < args.controls
        line = {"workload": args.workload, "seed": seed}
        t0 = time.perf_counter()
        if args.program:
            work_dir = tempfile.mkdtemp(prefix="calib_", dir=scratch)
            try:
                run = bench_run.run_program(
                    cell, seed, args.seconds, False, work_dir
                )
            finally:
                shutil.rmtree(work_dir, ignore_errors=True)
            line.update({
                "program_s": time.perf_counter() - t0,
                "setup_s": run["opened_at"] - bench_run.T_PROCESS,
                "window_seconds": [
                    r["round_seconds"] for r in run["window_rows"]
                ],
                "memory": run["memory"],
            })
        else:
            run = inputs(cell, seed)
        t1 = time.perf_counter()
        ref_run = follow(cell, run, subset_sums=i < args.faults)
        line["reference_s"] = time.perf_counter() - t1
        params0 = run["params0"]
        ref = compare.record(
            ref_run["test_loss"], ref_run["client_loss"],
            ref_run.pop("params"), params0,
        )
        line["test_loss"] = {"reference": ref["test_loss"]}
        if args.program:
            program = bench_run.program_record(run)
            line["test_loss"]["program"] = program["test_loss"]
            line["program"] = compare.numbers(program, ref)
            del program
            run["checkpoint"] = None
            gc.collect()
        for name in list(ref_run.get("subset_sums", {})):
            fault = {
                **ref,
                "delta": compare.deltas(fault_mean(ref_run, name), params0),
            }
            line[f"fault_only_{name}"] = compare.numbers(fault, ref)
            del fault
            gc.collect()
        del ref_run
        if planted:
            spec = bench_run.reference_spec(cell, run)
            clients = spec["task"].clients(
                run["data"], run["config"].worker_number, run["config"].seed
            )
            start = algo_ref.start_loss(
                model_ref.forward, config_file["model"], params0, clients,
                spec,
            )
            line["test_loss"]["start"] = start
            # ``compare.unchanged_record`` through ``compare.numbers``
            # without its 30 s over 841 M zeros: no parameter moves, so
            # the three parameter numbers read 1 by their definitions,
            # and the test loss stays at its start.
            line["fault_state_unchanged"] = {
                **{f"test_loss_r{i}": abs(start - r) / abs(r)
                   for i, r in enumerate(ref["test_loss"])},
                "update_norm": 1.0, "update_norm_worst_leaf": 1.0,
                "update_direction": 1.0,
            }
            controls = [(f"control_{lower}", lower)]
            if args.products:
                controls.append((f"control_{lower}_products", "float32"))
            for name, state in controls:
                try:
                    t3 = time.perf_counter()
                    control = follow(
                        cell, run, precision=lower, state_precision=state
                    )
                    line[name + "_s"] = time.perf_counter() - t3
                    record = compare.record(
                        control["test_loss"], control["client_loss"],
                        control["params"], params0,
                    )
                    del control
                    line[name] = compare.numbers(record, ref)
                    del record
                except Exception as e:  # a crash sets no reading
                    line[name] = {"crashed": f"{type(e).__name__}: {e}"[:300]}
                gc.collect()
        del ref, run
        gc.collect()
        print(json.dumps(line), flush=True)
        lines.append(line)
        with open(os.path.join(
                out_dir, f"calib_{args.workload}.jsonl"), "a") as f:
            f.write(json.dumps(line) + "\n")
    if args.program:
        print("lower readings (largest over seeds):", file=sys.stderr)
        for name in sorted(lines[0]["program"]):
            worst = max(line["program"][name] for line in lines)
            print(f"  {name}: {worst:.6g}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
