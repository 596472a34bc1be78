"""FLOPs one trained token needs in this chip's share of Olmo-Hybrid
(Olmo-Hybrid-7B), from shapes.

Counts multiply-adds (2 FLOPs each) of the forward pass of what is held
here: in each linear layer (Gated DeltaNet) the projections (q, k, v, the
output gate g, out, and the decay's and beta's ``a``, ``b``), the short
convolutions' taps and the delta rule in its chunk form (:func:`gdn_chunk_macs`);
in the full layer the projections and the score and value products over
the ``i + 1`` keys query ``i`` sees; the SwiGLU of every layer; the head
over the rows of the vocabulary held; forward once, backward twice. Norms,
the gates' activations, the decay's exponentials, the softmax, the loss,
the optimizer, recomputation under ``jax.checkpoint`` and the server's
evaluation are not counted.

Also the operations and bytes of the linear layers' mixers in the FORWARD
passes of one round, for the roofline reader
(``metrics/gdn_roofline_pct.py``): a device trace gives an op the line of a
scope's code in the training step's first forward pass and in the server's
evaluation; the rematerialised forward and the backward pass carry the
line of the block's ``nn.remat`` call (PERF.md § 6, PR 29), so the reader
times, and :func:`gdn_forward` counts, the forward passes alone. Bytes are
what a pass has to move once: the mixer's weights (bf16), its input and
output (bf16), and q, k, v, the gate and the heads' output written and
read once (bf16).
"""

from __future__ import annotations

LINEAR = "linear_attention"
#: Positions per chunk of the delta rule (models/lm_parts.py DELTA_CHUNK).
CHUNK = 64


def _sizes(model: dict) -> dict:
    kinds = model["layer_types"]
    H = model["linear_num_key_heads"]
    return {
        "D": model["hidden_size"], "F": model["intermediate_size"],
        "HD": model["hidden_size"],  # num_attention_heads x head_dim
        "KV": model["num_key_value_heads"] * (
            model["hidden_size"] // model["num_attention_heads"]),
        "H": H, "dk": model["linear_key_head_dim"],
        "dv": model["linear_value_head_dim"],
        "K": H * model["linear_key_head_dim"],
        "Vl": H * model["linear_value_head_dim"],
        "taps": model["linear_conv_kernel_dim"], "V": model["vocab_rows"],
        "n_linear": sum(k == LINEAR for k in kinds),
        "n_full": sum(k != LINEAR for k in kinds),
        "L": len(kinds),
    }


def gdn_projection_macs(model: dict) -> float:
    """q, k, v, g, out and the decay's and beta's projections a token."""
    z = _sizes(model)
    return z["D"] * (2 * z["K"] + 3 * z["Vl"] + 2 * z["H"])


def gdn_chunk_macs(model: dict, chunk: int = CHUNK) -> float:
    """The delta rule a token in its chunk form, every head, counting the
    causal half of each ``[C, C]`` product: ``A`` and ``P`` (``C/2`` keys
    of ``d_k`` each), the triangular solve by substitution (``C^2 / 6``
    a position), ``W`` and ``U0`` from it (``C/2 (d_k + d_v)``), and the
    step against the ``[d_k, d_v]`` state (``W S``, ``Q S``, ``K^T U``:
    three ``d_k d_v``; ``P U``: ``C/2 d_v``)."""
    z = _sizes(model)
    dk, dv, C = z["dk"], z["dv"], chunk
    per_head = (C * dk + C * C / 6 + C / 2 * (dk + dv) + 3 * dk * dv
                + C / 2 * dv)
    return z["H"] * per_head


def gdn_macs(model: dict) -> float:
    """One linear layer's mixer a token, projections to output."""
    z = _sizes(model)
    conv = z["taps"] * (2 * z["K"] + z["Vl"])
    return gdn_projection_macs(model) + conv + gdn_chunk_macs(model)


def attention_macs(model: dict, length: int) -> float:
    """The full layer's attention a token: projections, and scores and
    values over the mean ``(T + 1) / 2`` keys a query sees."""
    z = _sizes(model)
    project = z["D"] * (2 * z["HD"] + 2 * z["KV"])  # q, out; k, v
    return project + 2 * z["HD"] * (length + 1) / 2


def forward_macs(model: dict, length: int) -> float:
    z = _sizes(model)
    return (
        z["n_linear"] * gdn_macs(model)
        + z["n_full"] * attention_macs(model, length)
        + z["L"] * 3 * z["D"] * z["F"]
        + z["D"] * z["V"]
    )


def train_flops_per_sample(model: dict, shape) -> float:
    """Per trained token (the task's unit of work); ``shape`` ``[T]``."""
    return 3 * 2 * forward_macs(model, shape[0])


def gdn_forward(model: dict, tokens: int, passes: int):
    """``(FLOPs, bytes)`` of the linear layers' mixers, projections to
    output, in ``passes`` forward passes over ``tokens`` tokens in all."""
    z = _sizes(model)
    weights = gdn_projection_macs(model) + z["taps"] * (2 * z["K"] + z["Vl"])
    moved = 2 * z["D"] + 2 * (2 * z["K"] + 3 * z["Vl"])  # x, y; q k v g o
    return (
        2 * gdn_macs(model) * tokens * z["n_linear"],
        z["n_linear"] * 2 * (passes * weights + tokens * moved),
    )
