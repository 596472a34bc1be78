"""Plain reference: AFMoE's block stack (Trinity-Mini), one chip's share.

The published architecture (https://huggingface.co/arcee-ai/Trinity-Mini
``config.json``, ``model_type`` ``afmoe``; what the config has no key for
follows the family's public modelling code, transformers
``models/afmoe/modeling_afmoe.py``, and is listed under ``assumed`` in the
configuration's file): ``h = table[tokens] * sqrt(hidden_size)``
(``mup_enabled``); every layer ``h <- h + N2(Attn(N1 h))``, ``h <- h +
N4(MLP(N3 h))``, four RMS norms with a learned scale; attention over 32
query heads in groups over 4 key/value heads, ``q`` and ``k`` RMS-normed
per head, a sigmoid gate per output channel before ``W_o``; layer ``l`` of
``layer_types`` ``sliding_attention`` puts rotary positions on ``q`` and
``k`` (all of a head's dims, rotate-half) and lets query ``i`` see key
``j`` iff ``0 <= i - j < sliding_window``; a ``full_attention`` layer has
no positions and sees ``j <= i``; the first ``num_dense_layers`` layers'
MLP is a SwiGLU of width ``intermediate_size``, the others' is ``Shared(x)
+ sum_e w_e Expert_e(x)`` with ``s = sigmoid(W_r x)``, the chosen ``e`` the
``num_experts_per_tok`` largest of ``s + b`` (``b`` an untrained selection
bias), ``w = s[chosen] / (sum s[chosen] + 1e-20) * route_scale``; final
RMS norm, untied head.

``model`` (the configuration's ``"model"`` object) says what is held here
of a layer that several chips share: ``experts_held`` of ``num_experts``
starting at ``expert_offset`` and ``vocab_rows`` of the vocabulary. The
router scores all ``num_experts``; what experts that are not held would add
is left out; attention, the shared expert and the dense layer are whole.

Everything is ``jax.numpy`` in float32. The window is a MASK over all the
keys (no band logic: the mask is the definition); the scores are
materialised a block of queries at a time, against every key, so that 32
heads over 8,192 positions fit; the experts are a loop (``lax.scan``) over
the ones held, each over every token with the routing weight as a mask.
Products are built from what ``fed.primitives`` hands in (``dense``, ``q``,
``precision``). Imports nothing of the program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256  # queries whose scores over every key are live at once
SLIDING = "sliding_attention"
#: Standard deviation of the untrained selection bias (``assumed``).
SELECTION_BIAS_STD = 0.01


def layout(model: dict, shape) -> dict:
    """``{path: (shape, kind)}`` of every parameter tensor held here
    (``harness/data.py``). The embedding is N(0, 1/hidden) so that the
    stream after the ``sqrt(hidden)`` multiplier has unit variance."""
    D, hd, V = model["hidden_size"], model["head_dim"], model["vocab_rows"]
    HD = model["num_attention_heads"] * hd
    KV = model["num_key_value_heads"] * hd
    E, Eh = model["num_experts"], model["experts_held"]
    out = {
        ("embed", "table"): ((V, D), {"std": 1.0 / math.sqrt(D)}),
        ("final_norm", "scale"): ((D,), "ones"),
        ("lm_head", "kernel"): ((D, V), "kernel"),
    }
    for layer in range(model["num_hidden_layers"]):
        p = f"layer_{layer}"
        for norm in ("attn_norm", "attn_post_norm", "mlp_norm",
                     "mlp_post_norm"):
            out[(p, norm, "scale")] = ((D,), "ones")
        for name, width in (("q", HD), ("k", KV), ("v", KV), ("g", HD)):
            out[(p, "attn", name)] = ((D, width), "kernel")
        out[(p, "attn", "o")] = ((HD, D), "kernel")
        out[(p, "attn", "q_norm")] = ((hd,), "ones")
        out[(p, "attn", "k_norm")] = ((hd,), "ones")
        if layer < model["num_dense_layers"]:
            F = model["intermediate_size"]
            out[(p, "mlp", "gate")] = ((D, F), "kernel")
            out[(p, "mlp", "up")] = ((D, F), "kernel")
            out[(p, "mlp", "down")] = ((F, D), "kernel")
            continue
        F = model["moe_intermediate_size"]
        out[(p, "moe", "router")] = ((D, E), "kernel")
        out[(p, "moe", "bias")] = ((E,), {"std": SELECTION_BIAS_STD})
        out[(p, "moe", "gate")] = ((Eh, D, F), {"fan_in": D})
        out[(p, "moe", "up")] = ((Eh, D, F), {"fan_in": D})
        out[(p, "moe", "down")] = ((Eh, F, D), {"fan_in": F})
        out[(p, "moe", "shared_gate")] = ((D, F), "kernel")
        out[(p, "moe", "shared_up")] = ((D, F), "kernel")
        out[(p, "moe", "shared_down")] = ((F, D), "kernel")
    return out


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """``x`` ``[B, T, heads, d]``: position ``t`` turns the pair ``(x_i,
    x_(i + d/2))`` by ``t * theta^(-2i/d)``."""
    T, d = x.shape[1], x.shape[-1]
    inverse = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inverse[None, :]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(model, p, x, dense, q, precision, sliding: bool):
    B, T, _ = x.shape
    hd, H = model["head_dim"], model["num_attention_heads"]
    Hkv = model["num_key_value_heads"]
    eps = model["rms_norm_eps"]
    qh = rms_norm(dense(x, p["q"]).reshape(B, T, H, hd), p["q_norm"], eps)
    kh = rms_norm(dense(x, p["k"]).reshape(B, T, Hkv, hd), p["k_norm"], eps)
    vh = dense(x, p["v"]).reshape(B, T, Hkv, hd)
    if sliding:
        qh = rotary(qh, model["rope_theta"])
        kh = rotary(kh, model["rope_theta"])
    # Query head h reads key/value head h // (H / Hkv).
    kh = jnp.repeat(kh, H // Hkv, axis=2)
    vh = jnp.repeat(vh, H // Hkv, axis=2)
    block = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    window = model["sliding_window"]

    @jax.checkpoint
    def one_block(args):
        qs, first = args  # [B, block, H, hd]
        s = q(jnp.einsum("bthd,bshd->bhts", q(qs), q(kh),
                         precision=precision)) / math.sqrt(hd)
        i = first + jnp.arange(block)[:, None]
        j = jnp.arange(T)[None, :]
        visible = j <= i
        if sliding:
            visible = visible & (i - j < window)
        w = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
        return q(jnp.einsum("bhts,bshd->bthd", q(w), q(vh),
                            precision=precision))

    blocks = jnp.moveaxis(qh.reshape(B, T // block, block, H, hd), 1, 0)
    o = jax.lax.map(one_block, (blocks, jnp.arange(T // block) * block))
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, H * hd)
    return dense(o * jax.nn.sigmoid(dense(x, p["g"])), p["o"])


def swiglu(x, gate, up, down, dense):
    return dense(jax.nn.silu(dense(x, gate)) * dense(x, up), down)


def route(model, p, x, dense):
    """Scores over every expert of the layer, the chosen ones' indices and
    their normalised, scaled weights."""
    scores = jax.nn.sigmoid(dense(x, p["router"]))
    _, index = jax.lax.top_k(scores + p["bias"], model["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, index, axis=-1)
    weight = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    return index, weight * model["route_scale"]


def moe(model, p, x, dense, q, precision, shared: bool = True):
    index, weight = route(model, p, x, dense)
    first = model.get("expert_offset", 0)
    held = model["experts_held"]
    if shared:
        out = swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"],
                     dense)
    else:
        out = jnp.zeros_like(x)

    @jax.checkpoint
    def one_expert(e, gate, up, down):
        # Every token through this expert, its routing weight (0 for a
        # token that did not choose it) as the mask. Recomputed in the
        # backward pass: 16 experts' intermediates over 8,192 tokens
        # are 3.2 GB, and the comparator keeps a second copy of the
        # model on the device beside the step.
        mine = jnp.sum(weight * (index == first + e), -1)
        return mine[..., None] * swiglu(x, gate, up, down, dense)

    def add_expert(out, expert):
        return out + one_expert(*expert), None

    out, _ = jax.lax.scan(
        add_expert, out,
        (jnp.arange(held),
         *(p[name][:held] for name in ("gate", "up", "down"))),
    )
    return out


def forward(model, params, tokens, dense, q, precision, **_):
    """Token ids ``[B, T]`` -> logits ``[B, T, vocab_rows]``."""
    eps = model["rms_norm_eps"]
    kw = {"dense": dense, "q": q, "precision": precision}

    def block(layer, lp, h):
        x = rms_norm(h, lp["attn_norm"]["scale"], eps)
        y = attention(model, lp["attn"], x, **kw,
                      sliding=model["layer_types"][layer] == SLIDING)
        h = h + rms_norm(y, lp["attn_post_norm"]["scale"], eps)
        x = rms_norm(h, lp["mlp_norm"]["scale"], eps)
        if layer < model["num_dense_layers"]:
            y = swiglu(x, lp["mlp"]["gate"], lp["mlp"]["up"],
                       lp["mlp"]["down"], dense)
        else:
            y = moe(model, lp["moe"], x, **kw)
        return h + rms_norm(y, lp["mlp_post_norm"]["scale"], eps)

    h = params["embed"]["table"][tokens] * math.sqrt(model["hidden_size"])
    for layer in range(model["num_hidden_layers"]):
        h = jax.checkpoint(functools.partial(block, layer))(
            params[f"layer_{layer}"], h
        )
    x = rms_norm(h, params["final_norm"]["scale"], eps)
    return dense(x, params["lm_head"]["kernel"])
