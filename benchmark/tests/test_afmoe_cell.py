"""The Trinity-Mini (AFMoE) cell rehearsed on the CPU through ``run.py``'s
own path at a tiny preset (hidden 32, one dense layer and a period of
four, 4 query heads over 2 key/value heads, window 16 in query blocks of
8, 4 of 16 experts top-4, 4 clients x 1 sequence of 48 positions, one
client in flight): the next-token task, the model's plain reference, the
one-in-flight comparator, the readers, and
``calibrate_one_in_flight.py``'s controls and planted faults. No number
read here is a device metric."""

import importlib.util
import json
import os
import shutil

import pytest
from conftest import BENCH_DIR, ROOT, last_line

import calibrate_one_in_flight as calibrate

MODEL = {
    "hidden_size": 32, "num_hidden_layers": 5, "num_dense_layers": 1,
    "layer_types": ["sliding_attention"] * 4 + ["full_attention"],
    "head_dim": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
    "sliding_window": 16, "rope_theta": 10000.0, "intermediate_size": 48,
    "num_experts": 16, "experts_held": 4, "expert_offset": 0,
    "num_experts_per_tok": 4, "moe_intermediate_size": 16,
    "route_scale": 2.826, "rms_norm_eps": 1e-5, "query_block": 8,
    "vocab_rows": 64,
}
LIMITS = {"test_loss_r0": 2e-3, "test_loss_r1": 2e-3,
          "client_loss_r0": 2e-3, "client_loss_r1": 2e-3,
          "update_norm": 0.03, "update_norm_worst_leaf": 0.1,
          "update_direction": 0.1, "client_share_gap": 0.4}
CELL = "afmoe_tiny_cell"
NEW_METRICS = {"swa_ms_per_round", "swa_roofline_pct",
               "afmoe_moe_ms_per_round", "afmoe_moe_roofline_pct",
               "afmoe_local_expert_assignments_per_token"}


def write_root(tmp: str) -> str:
    base = os.path.join(tmp, "benchmark")
    for sub in ("configs", "traffic", "workloads"):
        os.makedirs(os.path.join(base, sub))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    config = {
        "name": "afmoe_tiny", "model": MODEL, "task": "next_token",
        "reference": "afmoe", "flops": "afmoe", "matmul_dtype": "bfloat16",
        "scopes": {"module": "distributed_learning_simulator_tpu.models."
                             "afmoe", "lookup": "scope_of_line"},
        "data": {"shape": [48], "vocab": 64, "n_train": 4, "n_test": 2},
        "argv": [
            "--dataset_name", "markov_tokens", "--model_name", "afmoe",
            "--model_args", json.dumps({**MODEL, "dtype": "float32"}),
            "--worker_number", "4", "--epoch", "1", "--batch_size", "1",
            "--eval_batch_size", "1", "--optimizer_name", "sgd",
            "--learning_rate", "0.3", "--momentum", "0",
            "--local_compute_dtype", "float32",
        ],
    }
    with open(os.path.join(base, "configs", "afmoe_tiny.json"), "w") as f:
        json.dump(config, f)
    shutil.copy(
        os.path.join(BENCH_DIR, "traffic", "fed_one_in_flight.json"),
        os.path.join(base, "traffic", "fed_one_in_flight.json"),
    )
    with open(os.path.join(base, "workloads", CELL + ".json"), "w") as f:
        json.dump({
            "compare_rounds": 2, "reference_block_clients": 1,
            "reference_eval_block": 1, "trace_seconds": 0.3,
            "limits": LIMITS,
        }, f)
    bench["configs"] = [{
        "name": "afmoe_tiny", "source": "test", "reduced": [], "why": "test",
        "file": "benchmark/configs/afmoe_tiny.json",
    }]
    bench["workloads"] = [{
        "name": CELL, "config": "afmoe_tiny",
        "traffic": "fed_one_in_flight", "chips": 1, "why": "test",
    }]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = (
                [CELL] if metric["name"] in NEW_METRICS else [])
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_is_correct(bench_run, tmp_path, capsys, trace):
    root = write_root(str(tmp_path))
    rc = bench_run.main(
        ["--workload", CELL, "--seed", str(2**31 + 4321), "--seconds", "1",
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
    # the counters' reader reports: top-4 of 16 experts, 4 held.
    per_token = line["metrics"][
        "afmoe_local_expert_assignments_per_token"]["value"]
    assert 0.6 < per_token < 1.4
    assert line["metrics"]["host_syncs_per_round"]["value"] == 1.0
    assert not (NEW_METRICS - {
        "afmoe_local_expert_assignments_per_token"}) & set(line["metrics"])


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
        return [n for n, limit in LIMITS.items()
                if numbers.get(n, 0.0) > limit]

    assert not fails(line["program"]), line["program"]
    for name in ("control_float8_e4m3fn", "fault_only_first_half",
                 "fault_only_odd", "fault_state_unchanged"):
        assert fails(line[name]), (name, line[name])


def _flops():
    spec = importlib.util.spec_from_file_location(
        "afmoe_flops", os.path.join(BENCH_DIR, "flops", "afmoe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _cell_model():
    with open(os.path.join(
            BENCH_DIR, "configs", "trinity_mini_26b_l5_ep8_vp8.json")) as f:
        return json.load(f)


def test_flops_by_hand():
    flops, model = _flops(), _cell_model()["model"]
    # A query at position i sees min(i + 1, 2048) keys.
    assert flops.keys_seen(8192, 2048) == sum(
        min(i + 1, 2048) for i in range(8192)) / 8192 == 1792.125
    assert flops.keys_seen(8192) == 4096.5
    assert flops.keys_seen(1000, 2048) == 500.5
    project = 2048 * (3 * 4096 + 2 * 512)
    assert flops.projection_macs(model) == project == 27_262_976
    sliding = project + 2 * 4096 * 1792.125
    full = project + 2 * 4096 * 4096.5
    assert flops.attention_macs(model, 8192, True) == sliding
    routed = 2048 * 128 + 8 * 16 / 128 * 3 * 2048 * 1024
    assert flops.routed_macs(model) == routed == 6_553_600
    by_hand = (4 * sliding + full + 3 * 2048 * 6144
               + 4 * (routed + 3 * 2048 * 1024) + 2048 * 25024)
    assert flops.forward_macs(model, 8192) == by_hand
    assert flops.train_flops_per_sample(model, [8192]) == 6 * by_hand
    # One round: 4 clients x 1 step and 2 evaluation passes of 8,192.
    ops, moved = flops.swa_forward(model, 49152, 6, 8192)
    assert ops == 2 * sliding * 49152 * 4
    assert moved == 4 * 2 * (6 * project + 49152 * (
        2 * 2048 + 2 * (3 * 4096 + 2 * 512)))
    ops, moved = flops.moe_forward(model, 49152, 6)
    assert ops == 2 * routed * 49152 * 4
    assert moved == 4 * 2 * (6 * (2048 * 128 + 16 * 3 * 2048 * 1024)
                             + 49152 * 2 * 2048)


def test_the_configuration_states_its_source_and_its_cut():
    """Every number of the published config under its own key, changed
    only where ``reduced`` says; the widths as published; the share the
    program is told is the reference's."""
    config = _cell_model()
    published = {
        "hidden_size": 2048, "head_dim": 128, "num_attention_heads": 32,
        "num_key_value_heads": 4, "sliding_window": 2048,
        "intermediate_size": 6144, "moe_intermediate_size": 1024,
        "num_experts_per_tok": 8, "num_shared_experts": 1,
        "route_scale": 2.826, "rope_theta": 10000, "rms_norm_eps": 1e-5,
        "global_attn_every_n_layers": 4, "vocab_size": 200192,
        "num_experts": 128, "num_hidden_layers": 32, "num_dense_layers": 2,
    }
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers", "num_dense_layers",
                       "layer_types", "num_experts", "vocab_size"}
    for key, value in published.items():
        if key in reduced:
            assert config["published"][key] == value
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert set(config["reduced_from"]) == reduced
    model = config["model"]
    assert model["num_experts"] == 128 and model["experts_held"] == 16
    assert config["num_experts"] == 16 and config["vocab_size"] == 25024
    assert model["vocab_rows"] == config["data"]["vocab"] == 25024
    assert model["layer_types"] == config["layer_types"] == (
        ["sliding_attention"] * 4 + ["full_attention"])
    argv = config["argv"]
    assert json.loads(argv[argv.index("--model_args") + 1]) == model
    assert config["deployment"]["chips_sharing_a_layer"] == 8
    # The parameter count the file states is the layout's.
    spec = importlib.util.spec_from_file_location(
        "afmoe_ref", os.path.join(BENCH_DIR, "references", "afmoe.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    total = 0
    for shape, _ in ref.layout(model, config["data"]["shape"]).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    assert total == config["parameters"]["total"] == 705_474_304


def test_scope_reader_sums_the_lines_of_the_new_scopes():
    from distributed_learning_simulator_tpu.models import afmoe as af
    from harness import scopes

    path = af.__file__
    band = af.swa.__code__.co_firstlineno + 5
    experts = af.moe_experts.__code__.co_firstlineno + 2
    full = af.attn_full.__code__.co_firstlineno + 4
    named = {"scopes": {"module": af.__name__, "lookup": "scope_of_line"}}
    ctx = {"spec": {"config": named}, "trace": {"ops": {"periods": 2, "table": [
        {"name": "fusion.1", "seconds": 0.5, "source": f"{path}:{band}"},
        {"name": "fusion.2", "seconds": 0.25, "source": f"{path}:{experts}"},
        {"name": "fusion.3", "seconds": 1.0, "source": f"{path}:{full}"},
        {"name": "fusion.4", "seconds": 4.0,
         "source": f"solar_open2.py:{band}"},
    ]}}}
    assert scopes.ms_per_round(ctx, {"swa"}) == pytest.approx(250.0)
    assert scopes.ms_per_round(
        ctx, {"moe/route", "moe/experts"}) == pytest.approx(125.0)
    assert scopes.ms_per_round(ctx, {"attn_full"}) == pytest.approx(500.0)
    assert scopes.ms_per_round(ctx, {"kda"}) is None
