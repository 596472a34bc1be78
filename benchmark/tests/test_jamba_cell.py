"""The Jamba cell rehearsed on the CPU through ``run.py``'s own path at a
tiny preset (hidden 64, a period of 4 layers with attention at layer 1,
Mamba mixers over 32 of 128 channels with a state of 4 and a step rank
of 8, 2 of 4 query heads over one key/value head, 48 of 96 MLP columns,
2 clients x 1 sequence of 48 positions, one client in flight): the
next-token task, the model's plain reference, the one-in-flight
comparator, the readers, and ``calibrate_one_in_flight.py``'s controls and
planted faults. No number read here is a device metric."""

import importlib.util
import json
import os
import shutil

import pytest
from conftest import BENCH_DIR, ROOT, last_line

import calibrate_one_in_flight as calibrate

MODEL = {
    "hidden_size": 64, "num_hidden_layers": 4, "attn_layer_period": 4,
    "attn_layer_offset": 1, "num_attention_heads": 4,
    "num_key_value_heads": 1, "heads_held": 2, "mamba_expand": 2,
    "channels_held": 32, "mamba_d_state": 4, "mamba_d_conv": 4,
    "mamba_dt_rank": 8, "intermediate_size": 96, "mlp_columns_held": 48,
    "rms_norm_eps": 1e-6, "vocab_rows": 64,
}
# The tiny cell's limits, as the Trinity cell's test sets them: sound runs
# at this size read well inside them and the controls well outside.
LIMITS = {"test_loss_r0": 2e-3, "test_loss_r1": 2e-3,
          "client_loss_r0": 2e-3, "client_loss_r1": 2e-3,
          "update_norm": 0.03, "update_norm_worst_leaf": 0.1,
          "update_direction": 0.1, "client_share_gap": 0.4}
CELL = "jamba_tiny_cell"
NEW_METRICS = {"mamba_ms_per_round", "ssm_scan_ms_per_round",
               "ssm_scan_roofline_pct"}
CONFIG = "jamba2_3b_l14_tp4_vp8.json"


def write_root(tmp: str) -> str:
    base = os.path.join(tmp, "benchmark")
    for sub in ("configs", "traffic", "workloads"):
        os.makedirs(os.path.join(base, sub))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    config = {
        "name": "jamba_tiny", "model": MODEL, "task": "next_token",
        "reference": "jamba", "flops": "jamba",
        "matmul_dtype": "bfloat16",
        "scopes": {"module": "distributed_learning_simulator_tpu.models.jamba",
                   "lookup": "scope_of_line"},
        "data": {"shape": [48], "vocab": 64, "n_train": 2, "n_test": 2},
        "argv": [
            "--dataset_name", "markov_tokens", "--model_name", "jamba",
            "--model_args", json.dumps({**MODEL, "dtype": "float32"}),
            "--worker_number", "2", "--epoch", "1", "--batch_size", "1",
            "--eval_batch_size", "1", "--optimizer_name", "sgd",
            "--learning_rate", "0.3", "--momentum", "0",
            "--local_compute_dtype", "float32",
        ],
    }
    with open(os.path.join(base, "configs", "jamba_tiny.json"),
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
        "name": "jamba_tiny", "source": "test", "reduced": [],
        "why": "test", "file": "benchmark/configs/jamba_tiny.json",
    }]
    bench["workloads"] = [{
        "name": CELL, "config": "jamba_tiny",
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
        f"jamba_{kind}", os.path.join(BENCH_DIR, kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _cell_config():
    with open(os.path.join(BENCH_DIR, "configs", CONFIG)) as f:
        return json.load(f)


def test_flops_by_hand():
    flops, model = _module("flops", "jamba"), _cell_config()["model"]
    D, E, N, R, F, V = 2560, 1280, 16, 160, 2048, 8192
    project = 2 * D * E + E * (R + 2 * N) + R * E + E * D
    assert flops.mamba_projection_macs(model) == project == 10_280_960
    assert flops.scan_macs(model) == 2 * E * N
    mamba = project + 4 * E + 2 * E * N
    assert flops.mamba_macs(model) == mamba
    # 5 query heads of 128 over the one key/value head, 16,385 / 2 keys.
    attention = D * (2 * 640 + 2 * 128) + 2 * 640 * 16385 / 2
    assert flops.attention_macs(model, 16384) == attention
    by_hand = 13 * mamba + attention + 14 * 3 * D * F + D * V
    assert flops.forward_macs(model, 16384) == pytest.approx(by_hand)
    # 2.339 GFLOP a trained token (2.335 without W_dt and the taps).
    assert flops.train_flops_per_sample(model, [16384]) == pytest.approx(
        6 * by_hand) == pytest.approx(2.339e9, rel=1e-3)
    # One round: 2 clients x 1 step and 2 evaluation passes of 16,384.
    ops, moved = flops.mamba_forward(model, 65536, 4)
    assert ops == pytest.approx(2 * mamba * 65536 * 13)
    assert moved == 13 * (2 * 4 * (project + 4 * E) + 65536 * (
        4 * D + 16 * E + 4 * (R + 2 * N)))
    # The scan's kernel: one launch over one sequence of 16,384.
    ops, moved = flops.scan_forward(model, 16384, 1)
    assert ops == 4 * E * N * 16384
    assert moved == 16384 * 4 * (3 * E + 2 * N + N * E / 128) + 4 * (
        E * N + E)


def test_the_configuration_states_its_source_and_its_cut():
    """Every number of the published config under its own key, changed
    only where ``reduced`` says, and no width among them; the share the
    program is told is the reference's; the parameter count the file
    states is the layout's."""
    config = _cell_config()
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 8192, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
        "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1,
        "num_experts_per_tok": 1, "num_hidden_layers": 28,
        "num_key_value_heads": 1, "num_logits_to_keep": 1,
        "rms_norm_eps": 1e-6, "sliding_window": None,
        "tie_word_embeddings": True, "use_mamba_kernels": True,
        "vocab_size": 65536,
    }
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers", "num_attention_heads",
                       "vocab_size"}
    for key, value in published.items():
        if key in reduced:
            assert config["published"][key] == value
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert set(config["reduced_from"]) == reduced
    model = config["model"]
    for key in published:
        if key in model:
            assert model[key] == published[key] or key in (
                "num_hidden_layers",), key
    assert model["num_hidden_layers"] == config["num_hidden_layers"] == 14
    assert model["heads_held"] == config["num_attention_heads"] == 5
    assert model["channels_held"] * 4 == 2 * 2560
    assert model["mlp_columns_held"] * 4 == 8192
    assert model["vocab_rows"] == config["data"]["vocab"] == 8192
    # The three tensor-parallel cuts stated alike: a quarter each, the
    # held share the program's, and only the head count in ``reduced``.
    shares = {k: v for k, v in config["tensor_parallel_shares"].items()
              if k != "why"}
    assert len(shares) == 3
    for key, share in shares.items():
        assert share["held"] * 4 == share["published"], key
        assert model[share["model_key"]] == share["held"], key
        assert share["in_reduced"] == (key in reduced), key
    argv = config["argv"]
    assert json.loads(argv[argv.index("--model_args") + 1]) == model
    assert config["deployment"]["chips"] == 8
    assert config["deployment"]["tensor_parallel"] == 4
    assert config["deployment"]["vocab_parallel"] == 8
    ref = _module("references", "jamba")
    total = 0
    for shape, _ in ref.layout(model, config["data"]["shape"]).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    assert total == config["parameters"]["total"] == 379_216_576


def test_scope_and_kernel_readers():
    """``mamba_ms_per_round`` sums the lines of scopes ``mamba`` and
    ``ssm_scan``; ``ssm_scan_ms_per_round`` the forward kernel's launches
    by name (not the backward kernel's), and its roofline counts them."""
    from distributed_learning_simulator_tpu.models import jamba as jm
    from harness import scopes

    path = jm.__file__
    mamba = jm.mamba_out.__code__.co_firstlineno + 6
    scan = jm.ssm_scan.__code__.co_firstlineno + 3
    mlp = jm.mlp.__code__.co_firstlineno + 2
    named = {"scopes": {"module": jm.__name__, "lookup": "scope_of_line"}}
    table = [
        {"name": "fusion.1", "seconds": 0.5, "source": f"{path}:{mamba}"},
        {"name": "selective_scan_forward.3", "seconds": 0.25,
         "executions": 8, "source": f"{path}:{scan}"},
        {"name": "selective_scan_backward.4", "seconds": 0.75,
         "executions": 4, "source": "jamba.py:1"},
        {"name": "fusion.2", "seconds": 4.0, "source": f"{path}:{mlp}"},
    ]
    ctx = {"spec": {"config": named}, "trace": {"ops": {
        "periods": 2, "table": table}}}
    assert scopes.ms_per_round(ctx, {"mamba", "ssm_scan"}) == (
        pytest.approx(375.0))
    assert _module("metrics", "mamba_ms_per_round").read(ctx) == (
        pytest.approx(375.0))
    reader = _module("metrics", "ssm_scan_ms_per_round")
    assert reader.read(ctx) == pytest.approx(125.0)
    assert reader.launches(ctx) == pytest.approx((0.125, 4.0))
    assert reader.read({"trace": None}) is None
    config = _cell_config()
    ctx["spec"] = {"config": config, "traffic": {"argv": []}}
    ctx.update(clients=2, work_per_round=32768,
               peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    least = _module("flops", "jamba").scan_forward(
        config["model"], 4 * 16384, 4)[1] / 819e9
    assert _module("metrics", "ssm_scan_roofline_pct").read(ctx) == (
        pytest.approx(100 * least / 0.125))
