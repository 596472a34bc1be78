"""The Olmo-Hybrid cell rehearsed on the CPU through ``run.py``'s own path
at a tiny preset (hidden 64, a period ``[linear, linear, linear, full]``,
Gated DeltaNet over 2 heads with keys of 16 and values of 32, full
attention over 2 heads of 32, 4 clients x 1 sequence of 48 positions, one
client in flight): the next-token task, the model's plain reference, the
one-in-flight comparator, the readers, and ``calibrate_one_in_flight.py``'s
controls and planted faults. No number read here is a device metric."""

import importlib.util
import json
import os
import shutil

import pytest
from conftest import BENCH_DIR, ROOT, last_line

import calibrate_one_in_flight as calibrate

MODEL = {
    "hidden_size": 64, "num_hidden_layers": 4,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"],
    "num_attention_heads": 2, "num_key_value_heads": 2,
    "linear_num_key_heads": 2, "linear_num_value_heads": 2,
    "linear_key_head_dim": 16, "linear_value_head_dim": 32,
    "linear_conv_kernel_dim": 4, "intermediate_size": 96,
    "rms_norm_eps": 1e-6, "vocab_rows": 64,
}
# The tiny cell's limits, as the Trinity cell's test sets them: sound runs
# at this size read well inside them and the controls well outside.
LIMITS = {"test_loss_r0": 2e-3, "test_loss_r1": 2e-3,
          "client_loss_r0": 2e-3, "client_loss_r1": 2e-3,
          "update_norm": 0.03, "update_norm_worst_leaf": 0.1,
          "update_direction": 0.1, "client_share_gap": 0.4}
CELL = "olmo_hybrid_tiny_cell"
NEW_METRICS = {"gdn_ms_per_round", "gdn_roofline_pct"}
CONFIG = "olmo_hybrid_7b_l4_vp8.json"


def write_root(tmp: str) -> str:
    base = os.path.join(tmp, "benchmark")
    for sub in ("configs", "traffic", "workloads"):
        os.makedirs(os.path.join(base, sub))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    config = {
        "name": "olmo_hybrid_tiny", "model": MODEL, "task": "next_token",
        "reference": "olmo_hybrid", "flops": "olmo_hybrid",
        "matmul_dtype": "bfloat16",
        "scopes": {"module": "distributed_learning_simulator_tpu.models."
                             "olmo_hybrid", "lookup": "scope_of_line"},
        "data": {"shape": [48], "vocab": 64, "n_train": 4, "n_test": 2},
        "argv": [
            "--dataset_name", "markov_tokens", "--model_name", "olmo_hybrid",
            "--model_args", json.dumps({**MODEL, "dtype": "float32"}),
            "--worker_number", "4", "--epoch", "1", "--batch_size", "1",
            "--eval_batch_size", "1", "--optimizer_name", "sgd",
            "--learning_rate", "0.3", "--momentum", "0",
            "--local_compute_dtype", "float32",
        ],
    }
    with open(os.path.join(base, "configs", "olmo_hybrid_tiny.json"),
              "w") as f:
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
        "name": "olmo_hybrid_tiny", "source": "test", "reduced": [],
        "why": "test", "file": "benchmark/configs/olmo_hybrid_tiny.json",
    }]
    bench["workloads"] = [{
        "name": CELL, "config": "olmo_hybrid_tiny",
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
        ["--workload", CELL, "--seed", str(2**31 + 5311), "--seconds", "1",
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
    # A CPU trace has no device lanes: the scope readers return nothing
    # and their metrics are left out.
    assert line["metrics"]["host_syncs_per_round"]["value"] == 1.0
    assert not NEW_METRICS & set(line["metrics"])


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


def _module(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"olmo_{kind}", os.path.join(BENCH_DIR, kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _cell_config():
    with open(os.path.join(BENCH_DIR, "configs", CONFIG)) as f:
        return json.load(f)


def test_flops_by_hand():
    flops, model = _module("flops", "olmo_hybrid"), _cell_config()["model"]
    D, K, V, H, F = 3840, 30 * 96, 30 * 192, 30, 11008
    project = D * (2 * K + 3 * V + 2 * H)
    assert flops.gdn_projection_macs(model) == project == 88_704_000
    # A head and position: A and P over the causal half of 64 keys of 96,
    # the solve by substitution, W and U0, the step against [96 x 192].
    head = (64 * 96 + 64 * 64 / 6 + 32 * (96 + 192) + 3 * 96 * 192
            + 32 * 192)
    assert flops.gdn_chunk_macs(model) == pytest.approx(30 * head)
    gdn = project + 4 * (2 * K + V) + 30 * head
    assert flops.gdn_macs(model) == pytest.approx(gdn)
    full = 4 * D * D + 2 * D * 4097 / 2
    assert flops.attention_macs(model, 4096) == full
    by_hand = 3 * gdn + full + 4 * 3 * D * F + D * 12544
    assert flops.forward_macs(model, 4096) == pytest.approx(by_hand)
    assert flops.train_flops_per_sample(model, [4096]) == pytest.approx(
        6 * by_hand)
    # ISSUE 35's count: 5.42 GFLOP a trained token.
    assert 6 * by_hand == pytest.approx(5.42e9, rel=1e-3)
    # One round: 4 clients x 1 step and 2 evaluation passes of 4,096.
    ops, moved = flops.gdn_forward(model, 24576, 6)
    assert ops == pytest.approx(2 * gdn * 24576 * 3)
    assert moved == 3 * 2 * (6 * (project + 4 * (2 * K + V))
                             + 24576 * (2 * D + 2 * (2 * K + 3 * V)))


def test_the_configuration_states_its_source_and_its_cut():
    """Every number of the published config under its own key, changed
    only where ``reduced`` says; the widths as published; the share the
    program is told is the reference's; the parameter count the file
    states is the layout's."""
    config = _cell_config()
    published = {
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_attention_heads": 30, "num_key_value_heads": 30,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "max_position_embeddings": 65536, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": False, "attention_bias": False,
        "hidden_act": "silu", "model_type": "olmo_hybrid",
        "rope_parameters": {"rope_theta": None},
        "num_hidden_layers": 32, "vocab_size": 100352,
        "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 8,
    }
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers", "layer_types", "vocab_size"}
    for key, value in published.items():
        if key in reduced:
            assert config["published"][key] == value
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert set(config["reduced_from"]) == reduced
    model = config["model"]
    for key in published:
        if key in model and key not in reduced:
            assert model[key] == published[key], key
    assert model["vocab_rows"] == config["data"]["vocab"] == 12544
    assert model["layer_types"] == config["layer_types"] == (
        ["linear_attention"] * 3 + ["full_attention"])
    argv = config["argv"]
    assert json.loads(argv[argv.index("--model_args") + 1]) == model
    assert config["deployment"]["chips"] == 8
    assert config["deployment"]["vocab_parallel"] == 8
    ref = _module("references", "olmo_hybrid")
    total = 0
    for shape, _ in ref.layout(model, config["data"]["shape"]).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    assert total == config["parameters"]["total"] == 928_862_196


def test_scope_reader_sums_the_lines_of_the_new_scopes():
    from distributed_learning_simulator_tpu.models import olmo_hybrid as oh
    from harness import scopes

    path = oh.__file__
    gdn = oh.gdn.__code__.co_firstlineno + 6
    full = oh.attn_full.__code__.co_firstlineno + 5
    named = {"scopes": {"module": oh.__name__, "lookup": "scope_of_line"}}
    ctx = {"spec": {"config": named}, "trace": {"ops": {"periods": 2, "table": [
        {"name": "fusion.1", "seconds": 0.5, "source": f"{path}:{gdn}"},
        {"name": "fusion.2", "seconds": 0.25, "source": f"{path}:{full}"},
        {"name": "fusion.3", "seconds": 4.0,
         "source": f"solar_open2.py:{gdn}"},
    ]}}}
    assert scopes.ms_per_round(ctx, {"gdn"}) == pytest.approx(250.0)
    assert scopes.ms_per_round(ctx, {"attn_full"}) == pytest.approx(125.0)
    assert scopes.ms_per_round(ctx, {"kda"}) is None
