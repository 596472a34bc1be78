"""Plain reference: Olmo-Hybrid's block stack (Olmo-Hybrid-7B), one
pipeline stage of it.

The published architecture (https://huggingface.co/allenai/Olmo-Hybrid-7B
``config.json``, ``model_type`` ``olmo_hybrid``; what the config has no key
for follows the family's public modelling code, transformers
``models/olmo3`` and ``models/qwen3_next``, and is listed under ``assumed``
in the configuration's file): ``h = table[tokens]``; every layer ``h <- h +
N1(Mixer(h))``, ``h <- h + N2(MLP(h))`` (post-norms only: the mixer and the
MLP read the residual stream as it is), RMS norms with a learned scale.

* ``linear_attention`` layers, Gated DeltaNet over ``linear_num_key_heads``
  heads: ``q, k, v = SiLU(conv4(x W))`` (a causal depthwise convolution of
  ``linear_conv_kernel_dim`` taps over positions), ``q <- l2norm(q) /
  sqrt(d_k)``, ``k <- l2norm(k)``; ONE log decay a head and position, ``g_t
  = -exp(A_log) softplus(x W_a + dt_bias)``; ``b_t = 2 sigmoid(x W_b)``
  (``linear_allow_neg_eigval``); ``S_t = exp(g_t) (I - b_t k_t k_t^T)
  S_(t-1) + b_t k_t v_t^T`` with ``S`` ``[d_k, d_v]``, ``o_t = S_t^T q_t``;
  ``y = W_o (RMSNorm_(d_v)(o) * SiLU(x W_g))``, one norm scale shared by
  the heads.
* ``full_attention`` layers: ``q = RMSNorm(x W_q)``, ``k = RMSNorm(x W_k)``
  over the whole projection, ``v = x W_v``; causal softmax attention over
  as many key/value heads as query heads, no positions (``rope_theta``
  null: read as none), no gate; ``W_o``.
* every layer: ``MLP(x) = W_down(SiLU(x W_gate) * x W_up)``.
* final RMS norm, untied head.

``model`` (the configuration's ``"model"`` object) says what is held here:
``vocab_rows`` of the vocabulary (the embedding and the head are
vocabulary-parallel over the stages); the layers are whole.

Departures from the published model, all stated in the configuration's
``reduced`` and ``assumed``: one period of 4 layers of 32 and 1/8 of the
vocabulary (one pipeline stage); no positions on the full layers (the
config's ``rope_theta`` is null); ``A_log`` and ``dt_bias`` start at 0 and
every weight is drawn from the seed (no trained weights); the gated
output norm's gate is SiLU and its scale shared by the heads, as the
family's public Gated DeltaNet code has them.

Everything is ``jax.numpy`` in float32, written position by position where
the mathematics is: the delta rule is a ``lax.scan`` over positions (run in
blocks under ``jax.checkpoint`` so its backward pass keeps one state a
block, not one a position: the same steps, recomputed); attention
materialises its scores a block of queries at a time against every key,
so that 30 heads over 4,096 positions fit. Products are built from what
``fed.primitives`` hands in (``dense``, ``q``, ``precision``). Imports
nothing of the program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

SCAN_BLOCK = 64  # positions per checkpointed block of the recurrence
QUERY_BLOCK = 256  # queries whose scores over every key are live at once
LINEAR = "linear_attention"


def sizes(model: dict) -> dict:
    return {
        "D": model["hidden_size"], "F": model["intermediate_size"],
        "H": model["num_attention_heads"], "Hkv": model["num_key_value_heads"],
        "hd": model["hidden_size"] // model["num_attention_heads"],
        "Hl": model["linear_num_key_heads"],
        "dk": model["linear_key_head_dim"],
        "dv": model["linear_value_head_dim"],
        "conv": model["linear_conv_kernel_dim"], "V": model["vocab_rows"],
        "eps": model["rms_norm_eps"],
    }


def layout(model: dict, shape) -> dict:
    """``{path: (shape, kind)}`` of every parameter tensor held here
    (``harness/data.py``); ``A_log`` and ``dt_bias`` start at 0 (stated
    constants, as Solar-Open2's: a decay of ``exp(-softplus(x W_a))`` a
    position)."""
    z = sizes(model)
    D, F = z["D"], z["F"]
    K, Vl = z["Hl"] * z["dk"], z["Hl"] * z["dv"]
    HD, KV = z["H"] * z["hd"], z["Hkv"] * z["hd"]
    out = {
        ("embed", "table"): ((z["V"], D), {"std": 1.0}),
        ("final_norm", "scale"): ((D,), "ones"),
        ("lm_head", "kernel"): ((D, z["V"]), "kernel"),
    }
    for layer, kind in enumerate(model["layer_types"]):
        p = f"layer_{layer}"
        out[(p, "attn_post_norm", "scale")] = ((D,), "ones")
        out[(p, "mlp_post_norm", "scale")] = ((D,), "ones")
        for name, dims in (("gate", (D, F)), ("up", (D, F)),
                           ("down", (F, D))):
            out[(p, "mlp", name)] = (dims, "kernel")
        if kind == LINEAR:
            for name, width in (("q", K), ("k", K), ("v", Vl)):
                out[(p, "gdn", name)] = ((D, width), "kernel")
                out[(p, "gdn", "conv_" + name)] = (
                    (z["conv"], width), "kernel")
            out[(p, "gdn", "a")] = ((D, z["Hl"]), "kernel")
            out[(p, "gdn", "b")] = ((D, z["Hl"]), "kernel")
            out[(p, "gdn", "dt_bias")] = ((z["Hl"],), "zeros")
            out[(p, "gdn", "A_log")] = ((z["Hl"],), "zeros")
            out[(p, "gdn", "g")] = ((D, Vl), "kernel")
            out[(p, "gdn", "o_norm")] = ((z["dv"],), "ones")
            out[(p, "gdn", "o")] = ((Vl, D), "kernel")
        else:
            for name, width in (("q", HD), ("k", KV), ("v", KV)):
                out[(p, "attn", name)] = ((D, width), "kernel")
            out[(p, "attn", "o")] = ((HD, D), "kernel")
            out[(p, "attn", "q_norm")] = ((HD,), "ones")
            out[(p, "attn", "k_norm")] = ((KV,), "ones")
    return out


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def causal_conv(x, w):
    """Depthwise causal convolution over positions: ``y_t = sum_j w[j] *
    x_(t - (K-1) + j)``, zeros before the sequence; ``x`` ``[B, T, C]``,
    ``w`` ``[K, C]``."""
    taps = w.shape[0]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(
        padded[:, j:j + x.shape[1]] * w[j] for j in range(taps)
    )


def delta_rule(qh, kh, vh, log_decay, beta, precision):
    """``S_t = exp(g_t) (I - b_t k_t k_t^T) S_(t-1) + b_t k_t v_t^T``,
    ``o_t = S_t^T q_t``, ``S_0 = 0``, position by position. ``qh``, ``kh``
    ``[B, T, H, d_k]``, ``vh`` ``[B, T, H, d_v]``, ``log_decay`` and
    ``beta`` ``[B, T, H]``."""
    B, T, H, dk = qh.shape
    ein = functools.partial(jnp.einsum, precision=precision)

    def step(S, inp):
        qt, kt, vt, gt, bt = inp
        S = S * jnp.exp(gt)[..., None, None]
        u = bt[..., None] * (vt - ein("bhkv,bhk->bhv", S, kt))
        S = S + ein("bhk,bhv->bhkv", kt, u)
        return S, ein("bhkv,bhk->bhv", S, qt)

    xs = tuple(
        jnp.moveaxis(a, 1, 0) for a in (qh, kh, vh, log_decay, beta)
    )
    S0 = jnp.zeros((B, H, dk, vh.shape[-1]), jnp.float32)
    block = SCAN_BLOCK if T % SCAN_BLOCK == 0 else T

    @jax.checkpoint
    def run_block(S, blk):
        return jax.lax.scan(step, S, blk)

    xs = jax.tree_util.tree_map(
        lambda a: a.reshape((T // block, block) + a.shape[1:]), xs
    )
    _, out = jax.lax.scan(run_block, S0, xs)
    return jnp.moveaxis(out.reshape((T,) + out.shape[2:]), 0, 1)


def gdn(model, p, x, dense, q, precision):
    z = sizes(model)
    B, T, _ = x.shape
    H, dk, dv = z["Hl"], z["dk"], z["dv"]

    def short(name, width):
        y = jax.nn.silu(causal_conv(dense(x, p[name]), p["conv_" + name]))
        return y.reshape(B, T, H, width)

    qh = q(l2_norm(short("q", dk)) / math.sqrt(dk))
    kh = q(l2_norm(short("k", dk)))
    vh = q(short("v", dv))
    log_decay = -jnp.exp(p["A_log"]) * jax.nn.softplus(
        dense(x, p["a"]) + p["dt_bias"])
    beta = 2.0 * jax.nn.sigmoid(dense(x, p["b"]))
    o = delta_rule(qh, kh, vh, log_decay, beta, precision)
    gate = jax.nn.silu(dense(x, p["g"])).reshape(B, T, H, dv)
    o = rms_norm(o, p["o_norm"], z["eps"]) * gate
    return dense(o.reshape(B, T, H * dv), p["o"])


def attention(model, p, x, dense, q, precision):
    z = sizes(model)
    B, T, _ = x.shape
    H, Hkv, hd = z["H"], z["Hkv"], z["hd"]
    qh = rms_norm(dense(x, p["q"]), p["q_norm"], z["eps"]).reshape(
        B, T, H, hd)
    kh = rms_norm(dense(x, p["k"]), p["k_norm"], z["eps"]).reshape(
        B, T, Hkv, hd)
    vh = dense(x, p["v"]).reshape(B, T, Hkv, hd)
    # Query head h reads key/value head h // (H / Hkv).
    kh = jnp.repeat(kh, H // Hkv, axis=2)
    vh = jnp.repeat(vh, H // Hkv, axis=2)
    block = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T

    @jax.checkpoint
    def one_block(args):
        qs, first = args  # [B, block, H, hd]
        s = q(jnp.einsum("bthd,bshd->bhts", q(qs), q(kh),
                         precision=precision)) / math.sqrt(hd)
        visible = (first + jnp.arange(block)[:, None]
                   >= jnp.arange(T)[None, :])
        w = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
        return q(jnp.einsum("bhts,bshd->bthd", q(w), q(vh),
                            precision=precision))

    blocks = jnp.moveaxis(qh.reshape(B, T // block, block, H, hd), 1, 0)
    o = jax.lax.map(one_block, (blocks, jnp.arange(T // block) * block))
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, H * hd)
    return dense(o, p["o"])


def mlp(p, x, dense):
    return dense(jax.nn.silu(dense(x, p["gate"])) * dense(x, p["up"]),
                 p["down"])


def forward(model, params, tokens, dense, q, precision, **_):
    """Token ids ``[B, T]`` -> logits ``[B, T, vocab_rows]``."""
    eps = model["rms_norm_eps"]
    kw = {"dense": dense, "q": q, "precision": precision}

    def block(layer, lp, h):
        if model["layer_types"][layer] == LINEAR:
            y = gdn(model, lp["gdn"], h, **kw)
        else:
            y = attention(model, lp["attn"], h, **kw)
        h = h + rms_norm(y, lp["attn_post_norm"]["scale"], eps)
        y = mlp(lp["mlp"], h, dense)
        return h + rms_norm(y, lp["mlp_post_norm"]["scale"], eps)

    h = params["embed"]["table"][tokens]
    for layer in range(model["num_hidden_layers"]):
        h = jax.checkpoint(functools.partial(block, layer))(
            params[f"layer_{layer}"], h
        )
    x = rms_norm(h, params["final_norm"]["scale"], eps)
    return dense(x, params["lm_head"]["kernel"])
