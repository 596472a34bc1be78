"""FLOPs one trained token needs in this chip's share of Jamba
(AI21-Jamba2-3B), from shapes.

Counts multiply-adds (2 FLOPs each) of the forward pass of what is held
here: in each Mamba layer the four projections (``W_x_in``, ``W_z_in``,
``W_xproj``, ``W_out``), ``W_dt``, the convolution's taps and the scan
(:func:`scan_macs`); in the attention layer the projections and the score
and value products over the ``i + 1`` keys query ``i`` sees; the SwiGLU of
every layer; the tied head over the rows of the vocabulary held; forward
once, backward twice. Norms, activations, the scan's exponentials, the
softmax, the loss, the optimizer, recomputation and the server's
evaluation are not counted.

Also the operations and bytes the readers of the Mamba mixers and of the
scan's kernel need: :func:`mamba_forward` (``metrics/mamba_ms_per_round``
has no roofline; the counts are here for the next reader) and
:func:`scan_forward` (``metrics/ssm_scan_roofline_pct.py``).
"""

from __future__ import annotations

ATTENTION = "attention"
#: Positions a chunk of the scan: it saves its state at each chunk's start
#: (``ops/selective_scan.py`` CHUNK).
CHUNK = 128


def _sizes(model: dict) -> dict:
    period, offset = model["attn_layer_period"], model["attn_layer_offset"]
    kinds = [ATTENTION if i % period == offset else "mamba"
             for i in range(model["num_hidden_layers"])]
    D = model["hidden_size"]
    hd = D // model["num_attention_heads"]
    Hkv = model["num_key_value_heads"]
    kv = 1 if Hkv == 1 else model["heads_held"] // (
        model["num_attention_heads"] // Hkv)
    return {
        "D": D, "E": model["channels_held"], "N": model["mamba_d_state"],
        "R": model["mamba_dt_rank"], "taps": model["mamba_d_conv"],
        "F": model["mlp_columns_held"], "V": model["vocab_rows"],
        "HD": model["heads_held"] * hd, "KV": kv * hd,
        "n_mamba": kinds.count("mamba"), "n_attention": kinds.count(ATTENTION),
        "L": len(kinds),
    }


def mamba_projection_macs(model: dict) -> float:
    """``W_x_in``, ``W_z_in``, ``W_xproj``, ``W_dt`` and ``W_out`` a
    token."""
    z = _sizes(model)
    D, E = z["D"], z["E"]
    return 2 * D * E + E * (z["R"] + 2 * z["N"]) + z["R"] * E + E * D


def scan_macs(model: dict) -> float:
    """The scan a token: the state's update (``a s + b u``) and the ``C``
    contraction, one multiply-add each for every channel and state."""
    z = _sizes(model)
    return 2 * z["E"] * z["N"]


def mamba_macs(model: dict) -> float:
    """One Mamba mixer a token, projections to output."""
    z = _sizes(model)
    return mamba_projection_macs(model) + z["taps"] * z["E"] + scan_macs(
        model)


def attention_macs(model: dict, length: int) -> float:
    """The attention layer a token: projections, and scores and values
    over the mean ``(T + 1) / 2`` keys a query sees."""
    z = _sizes(model)
    project = z["D"] * (2 * z["HD"] + 2 * z["KV"])  # q, out; k, v
    return project + 2 * z["HD"] * (length + 1) / 2


def forward_macs(model: dict, length: int) -> float:
    z = _sizes(model)
    return (
        z["n_mamba"] * mamba_macs(model)
        + z["n_attention"] * attention_macs(model, length)
        + z["L"] * 3 * z["D"] * z["F"]
        + z["D"] * z["V"]
    )


def train_flops_per_sample(model: dict, shape) -> float:
    """Per trained token (the task's unit of work); ``shape`` ``[T]``."""
    return 3 * 2 * forward_macs(model, shape[0])


def mamba_forward(model: dict, tokens: int, passes: int):
    """``(FLOPs, bytes)`` of the Mamba mixers, projections to output, in
    ``passes`` forward passes over ``tokens`` tokens in all. Bytes moved
    once: the weights (bf16) a pass; a token's input and output (bf16),
    ``x``, ``z``, ``Delta`` and ``y`` (f32) and ``[delta, B, C]`` (f32)."""
    z = _sizes(model)
    weights = mamba_projection_macs(model) + z["taps"] * z["E"]
    moved = 2 * 2 * z["D"] + 4 * 4 * z["E"] + 4 * (z["R"] + 2 * z["N"])
    return (
        2 * mamba_macs(model) * tokens * z["n_mamba"],
        z["n_mamba"] * (2 * passes * weights + tokens * moved),
    )


def scan_forward(model: dict, tokens: int, passes: int):
    """``(FLOPs, bytes)`` of ``passes`` launches of the scan's forward
    kernel over ``tokens`` tokens in all (one launch: one layer, one
    batch of sequences): 2 FLOPs a multiply-add of :func:`scan_macs`
    (``exp`` not counted); bytes moved once, at their dtypes (f32): ``x``,
    ``Delta`` in and ``y`` out a token and channel, ``B`` and ``C`` a token
    and state, the state saved at each chunk's start, and ``A``, ``D`` a
    launch."""
    z = _sizes(model)
    E, N = z["E"], z["N"]
    per_token = 4 * (3 * E + 2 * N + N * E / CHUNK)
    return 2 * scan_macs(model) * tokens, (
        tokens * per_token + passes * 4 * (E * N + E))
