"""CPU set-up for the benchmark's own tests (``pytest benchmark/tests``).

Like ``tests/conftest.py``: JAX is held to the CPU with virtual devices
(four, for the mesh cell's wiring) before anything imports it. The
platform check of ``run.py`` is lifted here, by the tests, never by a
flag or variable of the script.
"""

import json
import os
import shutil
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
)

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

# Limits of the tiny cell: the same numbers the real cells hold, set as
# theirs are (PERF.md § 2) from CPU readings at this size (calibrate.py,
# seeds 1-11 and 2**31+77; controls and faults on the first four). Sound
# runs read at most test_loss 1.2e-4, client_loss 1.2e-4, update_norm
# 6.7e-3, worst leaf 0.026, update_direction 0.028, client_share_gap
# 0.0104. The reference in float8 (products and state) reads update_norm
# 0.87 or more; the mean over a named subset alone reads client_share_gap 1
# or more; a state left unchanged reads update_norm 1 by definition. (With
# the products alone in float8 update_direction reads 0.054-0.114 at this
# size, not three times a sound run: the tests do not lean on it. On the
# chip at the cell's size it reads 0.71-1.15 against a sound 0.026.)
TINY_LIMITS = {"test_loss_r0": 1e-3, "test_loss_r1": 1e-3,
               "client_loss_r0": 1e-3, "client_loss_r1": 1e-3,
               "update_norm": 0.05, "update_norm_worst_leaf": 0.3,
               "update_direction": 0.08, "client_share_gap": 0.2}

MESH_TRAFFIC = {
    "describes": "fed.json with the client axis sharded over four devices",
    "comparator": "fed",
    "argv": ["--mesh_devices", "4"],
}


def lower_precision_cell(cell: dict, dtype: str) -> dict:
    """The cell with the program's own lower-precision path switched on:
    its CNN takes the type it multiplies (and keeps activations) in as
    ``--model_args '{"dtype": ...}'``."""
    argv = list(cell["config"]["argv"])
    at = argv.index("--model_args")
    model_args = {**json.loads(argv[at + 1]), "dtype": dtype}
    argv[at + 1] = json.dumps(model_args)
    return {**cell, "config": {**cell["config"], "argv": argv}}


def write_tiny_root(tmp: str, traffic: str = "fed") -> str:
    """A checkout-shaped directory holding one tiny cell: the real
    ``BENCHMARK.json`` metrics, the real traffic files, and the repo's
    CNN at width 8 over 8 clients x 8 samples."""
    base = os.path.join(tmp, "benchmark")
    for sub in ("configs", "traffic", "workloads"):
        os.makedirs(os.path.join(base, sub))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    config = {
        "name": "tiny", "model": {"width": 8, "num_classes": 10},
        "reference": "cnn_tpu", "flops": "cnn_tpu",
        "matmul_dtype": "bfloat16",
        "data": {"shape": [32, 32, 3], "classes": 10, "n_train": 64,
                 "n_test": 32},
        "argv": [
            "--dataset_name", "cifar10", "--model_name", "cnn_tpu",
            "--model_args", '{"width": 8}', "--worker_number", "8",
            "--epoch", "1", "--batch_size", "4", "--client_chunk_size", "4",
            "--eval_batch_size", "32", "--optimizer_name", "sgd",
            "--learning_rate", "0.05", "--momentum", "0.9",
            "--local_compute_dtype", "float32",
        ],
    }
    with open(os.path.join(base, "configs", "tiny.json"), "w") as f:
        json.dump(config, f)
    shutil.copy(os.path.join(BENCH_DIR, "traffic", "fed.json"),
                os.path.join(base, "traffic", "fed.json"))
    with open(os.path.join(BENCH_DIR, "traffic", "fed.json")) as f:
        fed = json.load(f)
    with open(os.path.join(base, "traffic", "fed_mesh4.json"), "w") as f:
        json.dump({**MESH_TRAFFIC, "argv": fed["argv"] + MESH_TRAFFIC["argv"]},
                  f)
    with open(os.path.join(base, "workloads", "tiny_cell.json"), "w") as f:
        json.dump({
            "compare_rounds": 2, "reference_block_clients": 2,
            "reference_eval_block": 16, "trace_seconds": 0.3,
            "limits": TINY_LIMITS,
        }, f)
    bench["configs"] = [{
        "name": "tiny", "source": "test", "reduced": [], "why": "test",
        "file": "benchmark/configs/tiny.json",
    }]
    bench["workloads"] = [{
        "name": "tiny_cell", "config": "tiny", "traffic": traffic,
        "chips": 4 if traffic == "fed_mesh4" else 1, "why": "test",
    }]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = ["tiny_cell"]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


@pytest.fixture
def bench_run(monkeypatch):
    """``run.py`` with its look for a chip lifted (the CPU stands in) and
    a peak for the CPU's made-up device kind."""
    import jax

    import run

    def no_chip_check(chips):
        devices = jax.devices()
        return {"platform": devices[0].platform,
                "kind": devices[0].device_kind, "count": len(devices)}

    monkeypatch.setattr(run, "require_devices", no_chip_check)
    monkeypatch.setattr(
        run, "load_peaks",
        lambda: {jax.devices()[0].device_kind: {"bf16_flops_per_s": 1e12}},
    )
    return run


def last_line(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])
