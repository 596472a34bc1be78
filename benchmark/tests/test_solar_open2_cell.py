"""The Solar-Open2 cell rehearsed on the CPU through ``run.py``'s own
path at a tiny preset (hidden 32, 4 layers, 4 of 8 heads, 4 of 8 experts
top-2, 8 clients x 4 sequences of 32 positions, one client in flight):
the next-token task, the model's plain reference, the one-in-flight
comparator, the readers, and ``calibrate_one_in_flight.py``'s controls and
planted faults. No number read here is a device metric."""

import json
import os
import shutil

import pytest
from conftest import BENCH_DIR, ROOT, last_line

import calibrate_one_in_flight as calibrate

MODEL = {
    "hidden_size": 32, "num_hidden_layers": 4, "gqa_layers": [0],
    "head_dim": 8, "num_attention_heads": 8, "num_key_value_heads": 4,
    "heads_held": 4, "n_routed_experts": 8, "experts_held": 4,
    "expert_offset": 0, "num_experts_per_tok": 2,
    "moe_intermediate_size": 16, "vocab_rows": 64,
    "short_conv_kernel_size": 4, "gate_rank": 8, "rms_norm_eps": 1e-5,
}
LIMITS = {"test_loss_r0": 2e-3, "test_loss_r1": 2e-3,
          "client_loss_r0": 2e-3, "client_loss_r1": 2e-3,
          "update_norm": 0.03, "update_norm_worst_leaf": 0.1,
          "update_direction": 0.1, "client_share_gap": 0.4}
CELL = "solar_tiny_cell"


def write_root(tmp: str, dtype: str = "float32") -> str:
    base = os.path.join(tmp, "benchmark")
    for sub in ("configs", "traffic", "workloads"):
        os.makedirs(os.path.join(base, sub))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    model_args = {**MODEL, "dtype": dtype}
    config = {
        "name": "solar_tiny", "model": MODEL, "task": "next_token",
        "reference": "solar_open2", "flops": "solar_open2",
        "matmul_dtype": "bfloat16",
        "scopes": {"module": "distributed_learning_simulator_tpu.models."
                             "solar_open2", "lookup": "scope_of_line"},
        "data": {"shape": [32], "vocab": 64, "n_train": 32, "n_test": 8},
        "argv": [
            "--dataset_name", "markov_tokens", "--model_name", "solar_open2",
            "--model_args", json.dumps(model_args), "--worker_number", "8",
            "--epoch", "1", "--batch_size", "2", "--eval_batch_size", "4",
            "--optimizer_name", "sgd", "--learning_rate", "0.3",
            "--momentum", "0", "--local_compute_dtype", "float32",
        ],
    }
    with open(os.path.join(base, "configs", "solar_tiny.json"), "w") as f:
        json.dump(config, f)
    shutil.copy(
        os.path.join(BENCH_DIR, "traffic", "fed_one_in_flight.json"),
        os.path.join(base, "traffic", "fed_one_in_flight.json"),
    )
    with open(os.path.join(base, "workloads", CELL + ".json"), "w") as f:
        json.dump({
            "compare_rounds": 2, "reference_block_clients": 1,
            "reference_eval_block": 4, "trace_seconds": 0.3,
            "limits": LIMITS,
        }, f)
    bench["configs"] = [{
        "name": "solar_tiny", "source": "test", "reduced": [], "why": "test",
        "file": "benchmark/configs/solar_tiny.json",
    }]
    bench["workloads"] = [{
        "name": CELL, "config": "solar_tiny",
        "traffic": "fed_one_in_flight", "chips": 1, "why": "test",
    }]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [CELL]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_is_correct(bench_run, tmp_path, capsys, trace):
    root = write_root(str(tmp_path))
    rc = bench_run.main(
        ["--workload", CELL, "--seed", str(2**31 + 12345), "--seconds", "1",
         "--trace", str(trace)], root=root,
    )
    assert rc == 0
    line = last_line(capsys)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["compared"]) == set(LIMITS)
    if not trace:
        assert set(line["metrics"]) == {"setup_s", "client_rounds_per_s"}
        return
    # A CPU trace has no device lanes: the scope readers return nothing;
    # the counters' reader reports: top-2 of 8 experts, 4 held.
    per_token = line["metrics"]["local_expert_assignments_per_token"]["value"]
    assert 0.7 < per_token < 1.3
    assert line["metrics"]["host_syncs_per_round"]["value"] == 1.0
    assert not {"kda_ms_per_round", "kda_roofline_pct", "moe_ms_per_round",
                "moe_roofline_pct"} & set(line["metrics"])


def test_controls_and_planted_faults_fall_outside_the_limits(
        bench_run, tmp_path, capsys):
    """The reference in float8 in the program's place, the first half
    and the odd clients alone, a state handed back unchanged: each
    fails a held number; the program passes every one."""
    root = write_root(str(tmp_path))
    rc = calibrate.main(
        ["--workload", CELL, "--seeds", "7", "--seconds", "0.5"],
        root=root, out_dir=str(tmp_path / "out"),
    )
    assert rc == 0
    line = last_line(capsys)

    def fails(numbers):
        # ``client_share_gap`` is not among calibrate's numbers: the
        # comparator names no subset (its docstring says why).
        return [n for n, limit in LIMITS.items()
                if numbers.get(n, 0.0) > limit]

    assert not fails(line["program"]), line["program"]
    has_to_fail = ["control_float8_e4m3fn", "fault_only_first_half",
                   "fault_only_odd", "fault_state_unchanged"]
    for name in has_to_fail:
        assert fails(line[name]), (name, line[name])


def test_scope_reader_sums_the_lines_of_a_scope():
    """``harness/scopes.py`` over a made-up op table whose sources are
    lines of the model file."""
    from distributed_learning_simulator_tpu.models import solar_open2 as so
    from harness import scopes

    path = so.__file__
    kda = so.chunked_delta_rule.__code__.co_firstlineno + 30
    experts = so.moe_experts.__code__.co_firstlineno + 20
    named = {"scopes": {"module": so.__name__, "lookup": "scope_of_line"}}
    ctx = {"spec": {"config": named}, "trace": {"ops": {"periods": 2, "table": [
        {"name": "fusion.1", "seconds": 0.5, "source": f"{path}:{kda}"},
        {"name": "fusion.2", "seconds": 0.25,
         "source": f"{path}:{experts}"},
        {"name": "fusion.3", "seconds": 4.0, "source": "engine.py:112"},
        {"name": "fusion.4", "seconds": 1.0},
    ]}}}
    assert scopes.ms_per_round(ctx, {"kda"}) == pytest.approx(250.0)
    assert scopes.ms_per_round(
        ctx, {"moe/route", "moe/experts"}) == pytest.approx(125.0)
    assert scopes.ms_per_round(ctx, {"gqa"}) is None
    assert scopes.ms_per_round({"trace": None}, {"kda"}) is None
    # A configuration that names no scopes, or a program without the
    # module it names, has nothing to read.
    assert scopes.ms_per_round({**ctx, "spec": {"config": {}}}, {"kda"}) is None
    absent = {"scopes": {"module": "no_such_module", "lookup": "x"}}
    assert scopes.ms_per_round(
        {**ctx, "spec": {"config": absent}}, {"kda"}) is None
