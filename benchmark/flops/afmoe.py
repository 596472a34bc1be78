"""FLOPs one trained token needs in this chip's share of AFMoE
(Trinity-Mini), from shapes.

Counts multiply-adds (2 FLOPs each) of the forward pass of what is held
here: the attention projections (q, k, v, gate, out) of every layer; the
score and value products over the keys a query SEES, by the model's
definition: on a sliding layer query ``i`` its ``min(i + 1,
sliding_window)`` keys (not the block-granular band the program reads: a
block of 128 queries multiplies 2,176 keys and masks what the definition
leaves out, which is not counted), on a full layer its ``i + 1``; the
dense layer's SwiGLU; in each expert layer the router over every expert,
the shared expert and the routed experts held here in expectation
(``num_experts_per_tok * experts_held / num_experts`` a token: 1.0); the
head over the rows of the vocabulary held; forward once, backward twice.
Norms, rotary positions, the gates' sigmoids, the softmax, the loss, the
optimizer, recomputation under ``jax.checkpoint`` and the server's
evaluation are not counted.

Also the operations and bytes of the banded attention and of the
routed-expert part of the FORWARD passes of one round, for the roofline
readers (``metrics/swa_roofline_pct.py``,
``metrics/afmoe_moe_roofline_pct.py``): a device trace gives an op the
line of a scope's code in the training step's first forward pass and in
the server's evaluation; the rematerialised forward and the backward pass
carry the line of the block's ``nn.remat`` call (PERF.md § 6, PR 29), so
the readers time, and these functions count, the forward passes alone.
Bytes are what a pass has to move once: the part's weights (bf16), its
input and output activations (bf16) and, for attention, q, k, v, the gate
and the heads' output written and read once (bf16).
"""

from __future__ import annotations

SLIDING = "sliding_attention"


def _sizes(model: dict) -> dict:
    hd = model["head_dim"]
    kinds = model["layer_types"]
    dense = model["num_dense_layers"]
    return {
        "D": model["hidden_size"], "HD": model["num_attention_heads"] * hd,
        "KV": model["num_key_value_heads"] * hd,
        "F_dense": model["intermediate_size"],
        "F": model["moe_intermediate_size"], "E": model["num_experts"],
        "Eh": model["experts_held"], "K": model["num_experts_per_tok"],
        "V": model["vocab_rows"], "W": model["sliding_window"],
        "n_sliding": sum(k == SLIDING for k in kinds),
        "n_full": sum(k != SLIDING for k in kinds),
        "n_dense": dense, "n_expert": len(kinds) - dense,
    }


def keys_seen(length: int, window: int | None = None) -> float:
    """Mean over the queries of a sequence of the keys a query sees:
    ``i + 1`` at position ``i``, at most ``window``."""
    if window is None or window >= length:
        return (length + 1) / 2
    return (window * (window + 1) / 2 + (length - window) * window) / length


def projection_macs(model: dict) -> float:
    z = _sizes(model)
    return z["D"] * (3 * z["HD"] + 2 * z["KV"])  # q, gate, out; k, v


def attention_macs(model: dict, length: int, sliding: bool) -> float:
    """One layer's attention a token: projections, scores and values."""
    z = _sizes(model)
    seen = keys_seen(length, z["W"] if sliding else None)
    return projection_macs(model) + 2 * z["HD"] * seen


def routed_macs(model: dict) -> float:
    """Router over every expert plus the held experts' expected share."""
    z = _sizes(model)
    return z["D"] * z["E"] + z["K"] * z["Eh"] / z["E"] * 3 * z["D"] * z["F"]


def forward_macs(model: dict, length: int) -> float:
    z = _sizes(model)
    shared = 3 * z["D"] * z["F"]
    return (
        z["n_sliding"] * attention_macs(model, length, True)
        + z["n_full"] * attention_macs(model, length, False)
        + z["n_dense"] * 3 * z["D"] * z["F_dense"]
        + z["n_expert"] * (routed_macs(model) + shared)
        + z["D"] * z["V"]
    )


def train_flops_per_sample(model: dict, shape) -> float:
    """Per trained token (the task's unit of work); ``shape`` ``[T]``."""
    return 3 * 2 * forward_macs(model, shape[0])


def swa_forward(model: dict, tokens: int, passes: int, length: int):
    """``(FLOPs, bytes)`` of the sliding layers' attention, projections
    to output, in ``passes`` forward passes over ``tokens`` tokens in all,
    sequences of ``length``."""
    z = _sizes(model)
    weights = projection_macs(model)
    moved = 2 * z["D"] + 2 * (3 * z["HD"] + 2 * z["KV"])  # x, y; q k v g o
    return (
        2 * attention_macs(model, length, True) * tokens * z["n_sliding"],
        z["n_sliding"] * 2 * (passes * weights + tokens * moved),
    )


def moe_forward(model: dict, tokens: int, passes: int):
    """The router's and the held routed experts' part, every expert
    layer."""
    z = _sizes(model)
    weights = z["D"] * z["E"] + z["Eh"] * 3 * z["D"] * z["F"]
    return (
        2 * routed_macs(model) * tokens * z["n_expert"],
        z["n_expert"] * 2 * (passes * weights + tokens * 2 * z["D"]),
    )
