#!/usr/bin/env python3
"""Readings the limits of a cell are set from, on the chip, in one process.

``python benchmark/calibrate.py --workload <cell> --seeds 1,2,... [--controls
3] [--seconds 2]``. For every seed: the cell's own run (a short window;
the compared rounds are all in set-up) against the plain reference: the
lower readings. For the first ``--controls`` seeds also what has to fail,
each put in the program's place through the same comparison: the upper
readings. The controls are the reference in the next precision below the
one the configuration states, once for products, activations and the
clients' stored state alike and once for the products alone. The faults
are the reference's last mean taken over a named subset of the clients
alone (the other half left out, contiguous or every second client; one
chip's quarter standing for all) and a state handed back unchanged. One
JSON line per seed on standard output and in ``chiprun_out/``. Not run by
the benchmark's own runs.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import run as bench_run
from harness import compare, data as bench_data, spec as bench_spec

FAULT_SUBSETS = ("first_half", "odd", "run_0")


def main(argv=None, root: str = bench_run.ROOT,
         out_dir: str = os.path.join(bench_run.ROOT, "chiprun_out")) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--controls", type=int, default=3)
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
        work_dir = tempfile.mkdtemp(prefix="calib_", dir=scratch)
        t0 = time.perf_counter()
        try:
            run = bench_run.run_program(
                cell, seed, args.seconds, False, work_dir
            )
        finally:
            shutil.rmtree(work_dir, ignore_errors=True)
        t1 = time.perf_counter()
        ref_run = bench_run.follow_reference(cell, run)
        t2 = time.perf_counter()
        ref = bench_run.reference_record(ref_run, run["params0"])
        program = bench_run.program_record(run)
        line = {
            "workload": args.workload, "seed": seed,
            "program_s": t1 - t0, "reference_s": t2 - t1,
            "rounds_in_window": len(run["window_rows"]),
            "memory": run["memory"],
            "test_loss": {"program": program["test_loss"],
                          "reference": ref["test_loss"]},
            "program": compare.numbers(program, ref),
        }
        if i < args.controls:
            for name, state in ((f"control_{lower}", lower),
                                (f"control_{lower}_products", "float32")):
                try:
                    control = bench_run.follow_reference(
                        cell, run, precision=lower, state_precision=state
                    )
                    line[name] = compare.numbers(
                        bench_run.reference_record(control, run["params0"]),
                        ref,
                    )
                    del control
                except Exception as e:  # a crash sets no reading
                    line[name] = {"crashed": f"{type(e).__name__}: {e}"[:300]}
            for subset in FAULT_SUBSETS:
                line[f"fault_only_{subset}"] = compare.numbers(
                    compare.subset_record(ref, subset), ref
                )
            clients = bench_data.iid_clients(
                run["data"], run["config"].worker_number, run["config"].seed
            )
            start = algo_ref.start_loss(
                model_ref.forward, config_file["model"], run["params0"],
                clients, bench_run.reference_spec(cell, run),
            )
            line["test_loss"]["start"] = start
            line["fault_state_unchanged"] = compare.numbers(
                compare.unchanged_record(ref, start), ref
            )
        print(json.dumps(line), flush=True)
        lines.append(line)
        with open(os.path.join(
                out_dir, f"calib_{args.workload}.jsonl"), "a") as f:
            f.write(json.dumps(line) + "\n")
    names = sorted(lines[0]["program"])
    print("lower readings (largest over seeds):", file=sys.stderr)
    for name in names:
        worst = max(line["program"][name] for line in lines)
        print(f"  {name}: {worst:.6g}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
