#!/usr/bin/env python3
"""Federated next-token fine-tuning of one chip's share of a language
model (Solar-Open2, or with ``--config trinity_mini`` Trinity-Mini, with
``--config olmo_hybrid`` a pipeline stage of Olmo-Hybrid-7B, or with
``--config jamba`` a tensor-parallel rank of AI21-Jamba2-3B), through the
program's normal path: ``get_config(argv)`` and one
``run_simulation(config, dataset=...)``.

    python examples/solar_open2_share.py --size tiny   # CPU, seconds
    python examples/solar_open2_share.py --size cell   # one v5e chip
    python examples/solar_open2_share.py --config trinity_mini --size tiny
    python examples/solar_open2_share.py --config olmo_hybrid --size tiny
    python examples/solar_open2_share.py --config jamba --size tiny

``cell`` is the argv of the benchmark's configuration
(``benchmark/configs/solar_open2_250b_l4_ep40_tp8.json`` +
``traffic/fed_one_in_flight.json``): the published widths (hidden 4096,
heads of 128, experts of 1280, router 320 -> top-8), 8 of 320 experts, 8 of
64 heads, 24,576 of 196,608 vocabulary rows, one period of 4 layers; 841 M
parameters, one client in flight. ``tiny`` keeps the argv and shrinks the
model (hidden 64, 4 of 8 heads of 16, 4 of 8 experts top-2, vocabulary 96,
sequences of 64) so that a CPU runs it. ``--config trinity_mini`` is
``benchmark/configs/trinity_mini_26b_l5_ep8_vp8.json`` (``--model_name
afmoe``: banded sliding-window attention with rotary positions beside
gated full attention, a leading dense layer, 16 of 128 experts, 25,024
vocabulary rows, 705 M parameters, sequences of 8,192); its tiny preset
has a window of 16 in query blocks of 8 over 64 positions. ``--config
olmo_hybrid`` is ``benchmark/configs/olmo_hybrid_7b_l4_vp8.json``
(``--model_name olmo_hybrid``: Gated DeltaNet with one decay a head, keys
of 96 and values of 192, beside ungated full attention, a dense post-norm
block, 12,544 vocabulary rows, 929 M parameters, sequences of 4,096); its
tiny preset has 2 heads, keys of 16 and values of 32. ``--config jamba``
is ``benchmark/configs/jamba2_3b_l14_tp4_vp8.json`` (``--model_name
jamba``: Mamba-1 mixers, a selective scan over 1,280 of 5,120 channels
with a state of 16, beside MQA over 5 of 20 heads, a tied head, 8,192
vocabulary rows, 379 M parameters, sequences of 16,384); its tiny preset
holds 32 of 128 channels with a state of 4. The data is the benchmark's seeded
Markov source (``benchmark/tasks/next_token.py``); token datasets are
handed in, the dataset registry holds images.
"""

import argparse
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TINY_MODEL = {
    "hidden_size": 64, "num_hidden_layers": 4, "gqa_layers": [0],
    "head_dim": 16, "num_attention_heads": 8, "num_key_value_heads": 4,
    "heads_held": 4, "n_routed_experts": 8, "experts_held": 4,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "vocab_rows": 96, "gate_rank": 8, "dtype": "float32",
}
TINY_DATA = {"shape": [64], "vocab": 96, "n_train": 16, "n_test": 8}
TINY_AFMOE = {
    "hidden_size": 64, "head_dim": 16, "num_attention_heads": 8,
    "num_key_value_heads": 2, "sliding_window": 16, "query_block": 8,
    "intermediate_size": 96, "num_experts": 16, "experts_held": 4,
    "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "vocab_rows": 96, "dtype": "float32",
}
TINY_OLMO = {
    "hidden_size": 64, "num_attention_heads": 2, "num_key_value_heads": 2,
    "linear_num_key_heads": 2, "linear_num_value_heads": 2,
    "linear_key_head_dim": 16, "linear_value_head_dim": 32,
    "intermediate_size": 96, "vocab_rows": 96, "dtype": "float32",
}
TINY_JAMBA = {
    "hidden_size": 64, "num_hidden_layers": 4, "attn_layer_period": 4,
    "attn_layer_offset": 1, "num_attention_heads": 4, "heads_held": 2,
    "channels_held": 32, "mamba_d_state": 4, "mamba_dt_rank": 8,
    "intermediate_size": 96, "mlp_columns_held": 48, "vocab_rows": 96,
    "dtype": "float32",
}
CONFIGS = {
    "solar_open2": ("solar_open2_250b_l4_ep40_tp8", TINY_MODEL),
    "trinity_mini": ("trinity_mini_26b_l5_ep8_vp8", TINY_AFMOE),
    "olmo_hybrid": ("olmo_hybrid_7b_l4_vp8", TINY_OLMO),
    "jamba": ("jamba2_3b_l14_tp4_vp8", TINY_JAMBA),
}


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--size", choices=("tiny", "cell"), default="tiny")
    parser.add_argument("--config", choices=sorted(CONFIGS),
                        default="solar_open2")
    parser.add_argument("--round", type=int, default=3)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    if args.size == "tiny":
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    bench = os.path.join(ROOT, "benchmark")
    name, tiny_model = CONFIGS[args.config]
    with open(os.path.join(bench, "configs", name + ".json")) as f:
        config_file = json.load(f)
    with open(os.path.join(bench, "traffic", "fed_one_in_flight.json")) as f:
        traffic = json.load(f)
    argv = list(config_file["argv"]) + list(traffic["argv"])
    data_spec = config_file["data"]
    if args.size == "tiny":
        argv[argv.index("--model_args") + 1] = json.dumps(tiny_model)
        argv[argv.index("--local_compute_dtype") + 1] = "float32"
        data_spec = {**TINY_DATA, "n_train": 2 * int(
            argv[argv.index("--worker_number") + 1])}
    argv += ["--round", str(args.round), "--seed", str(args.seed),
             "--telemetry_level", "basic"]

    spec = importlib.util.spec_from_file_location(
        "next_token", os.path.join(bench, "tasks", "next_token.py"))
    task = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(task)

    from distributed_learning_simulator_tpu.config import get_config
    from distributed_learning_simulator_tpu.simulator import run_simulation

    config = get_config(argv)
    dataset = task.program_dataset(
        config.dataset_name, task.make(args.seed, data_spec), data_spec
    )
    result = run_simulation(config, dataset=dataset)
    for row in result["history"]:
        print(json.dumps({k: row[k] for k in (
            "round", "test_loss", "mean_client_loss", "round_seconds",
            "expert_load",
        ) if k in row}))


if __name__ == "__main__":
    main()
