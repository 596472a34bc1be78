"""FLOPs one trained token needs in this chip's share of Solar-Open2,
from shapes.

Counts multiply-adds (2 FLOPs each) of the forward pass of what is held
here (``references/solar_open2.py``'s ``sizes``): the projections, the
causal score and value products at the mean visible length ``T / 2``, the
delta rule by its step form (three ``[d, d]`` products and the decay a
position and head: not what chunking adds), the router over every expert,
the shared expert, the routed experts held here in expectation
(``num_experts_per_tok * experts_held / n_routed_experts`` a token) and
the head over the rows of the vocabulary held; forward once, backward
twice. Norms, gates' sigmoids, the short convolutions' taps, the loss,
the optimizer, recomputation under ``jax.checkpoint`` and the server's
evaluation are not counted (the convolutions: 12 K of 18.5 M a layer).

Also the operations and bytes of the KDA part and of the routed-expert
part of the FORWARD passes of one round, for the two roofline readers
(``metrics/kda_roofline_pct.py``, ``metrics/moe_roofline_pct.py``): a
device trace gives an op the line of a scope's code in the training
step's first forward pass and in the server's evaluation; the
rematerialised forward and the backward pass carry the line of the
block's ``nn.remat`` call, whichever part they belong to (PERF.md § 6,
PR 29), so the readers time, and these functions count, the forward
passes alone. Bytes are what a pass has to move once: the part's weights
read (bf16) and its input and output activations (bf16).
"""

from __future__ import annotations


def _held(model: dict) -> dict:
    group = model["num_attention_heads"] // model["num_key_value_heads"]
    return {
        "D": model["hidden_size"],
        "HD": model["heads_held"] * model["head_dim"],
        "KV": model["heads_held"] // group * model["head_dim"],
        "hd": model["head_dim"], "H": model["heads_held"],
        "R": model["gate_rank"], "F": model["moe_intermediate_size"],
        "E": model["n_routed_experts"], "Eh": model["experts_held"],
        "K": model["num_experts_per_tok"], "V": model["vocab_rows"],
        "n_gqa": len(model["gqa_layers"]),
        "n_kda": model["num_hidden_layers"] - len(model["gqa_layers"]),
        "L": model["num_hidden_layers"],
    }


def gqa_macs(model: dict, length: int) -> float:
    z = _held(model)
    project = z["D"] * (3 * z["HD"] + 2 * z["KV"])  # q, gate, out; k, v
    return project + 2 * z["HD"] * (length / 2)  # scores and values


def kda_macs(model: dict) -> float:
    z = _held(model)
    project = 4 * z["D"] * z["HD"]  # q, k, v, out
    gates = 2 * (z["D"] * z["R"] + z["R"] * z["HD"]) + z["D"] * z["H"]
    recurrence = z["H"] * 3.5 * z["hd"] * z["hd"]  # S k, k u^T, S q; decay
    return project + gates + recurrence


def routed_macs(model: dict) -> float:
    """Router over every expert plus the held experts' expected share."""
    z = _held(model)
    return z["D"] * z["E"] + z["K"] * z["Eh"] / z["E"] * 3 * z["D"] * z["F"]


def forward_macs(model: dict, length: int) -> float:
    z = _held(model)
    shared = 3 * z["D"] * z["F"]
    return (
        z["n_gqa"] * gqa_macs(model, length) + z["n_kda"] * kda_macs(model)
        + z["L"] * (routed_macs(model) + shared) + z["D"] * z["V"]
    )


def train_flops_per_sample(model: dict, shape) -> float:
    """Per trained token (the task's unit of work); ``shape`` ``[T]``."""
    return 3 * 2 * forward_macs(model, shape[0])


def _forward(model: dict, macs: float, weights: float, layers: int,
             tokens: int, passes: int):
    """``(FLOPs, bytes)`` of ``passes`` forward passes over ``tokens``
    tokens in all through ``layers`` layers of a part."""
    io = 2 * tokens * model["hidden_size"] * 2  # in and out, bf16
    return 2 * macs * tokens * layers, layers * (passes * 2 * weights + io)


def kda_forward(model: dict, tokens: int, passes: int):
    """The KDA layers' part of ``passes`` forward passes, ``tokens`` tokens."""
    z = _held(model)
    weights = 4 * z["D"] * z["HD"] + 2 * (
        z["D"] * z["R"] + z["R"] * z["HD"]) + z["D"] * z["H"]
    return _forward(model, kda_macs(model), weights, z["n_kda"], tokens,
                    passes)


def moe_forward(model: dict, tokens: int, passes: int):
    """The router's and the held routed experts' part, every layer."""
    z = _held(model)
    weights = z["D"] * z["E"] + z["Eh"] * 3 * z["D"] * z["F"]
    return _forward(model, routed_macs(model), weights, z["L"], tokens,
                    passes)
