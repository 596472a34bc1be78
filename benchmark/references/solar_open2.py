"""Plain reference: Solar-Open2's block stack, one chip's share of it.

The published architecture (https://huggingface.co/upstage/Solar-Open2-250B,
``config.json``): pre-norm blocks ``h <- h + Mixer_l(RMSNorm(h))``, ``h <- h
+ MoE(RMSNorm(h))``; layer ``l`` mixes by gated softmax attention without
positions (GQA) when ``l`` is in ``gqa_layers`` and by the gated delta rule
with a per-channel decay ("KDA", Kimi Linear, arXiv:2510.26692) otherwise;
every layer has sigmoid-routed experts, the chosen scores normalised, plus
one shared expert; untied embedding and head, final RMSNorm.

``model`` (the configuration's ``"model"`` object) says what is held here
of a layer that several chips share: ``heads_held`` of ``num_attention_heads``
(query heads of the GQA layers with their ``heads_held * num_key_value_heads
/ num_attention_heads`` key/value heads, and as many KDA heads),
``experts_held`` of ``n_routed_experts`` starting at ``expert_offset``, and
``vocab_rows`` of the vocabulary. The router scores all ``n_routed_experts``
and picks ``num_experts_per_tok`` of them; what experts and heads that are
not held would add is left out; the shared expert is whole.

Everything is ``jax.numpy`` in float32, written position by position where
the mathematics is: the delta rule is a ``lax.scan`` over positions (run in
blocks under ``jax.checkpoint`` so its backward pass keeps one state a
block, not one a position: the same steps, recomputed), attention
materialises its scores one sequence at a time, the experts are a loop
(``lax.scan``: one expert's code, compiled once) over the ones held, each
over every token with the routing weight as a mask. Products are built from
what ``fed.primitives`` hands in (``dense``, ``q``, ``precision``): the
reference multiplies f32 at ``highest``; a control rounds the operands and
results of every product. The recurrence's state stays f32 in either.
Imports nothing of the program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

SCAN_BLOCK = 64  # positions per checkpointed block of the recurrence


def sizes(model: dict) -> dict:
    hd = model["head_dim"]
    held = model["heads_held"]
    group = model["num_attention_heads"] // model["num_key_value_heads"]
    if held % group:
        raise ValueError(
            f"{held} query heads held do not make whole groups of {group}"
        )
    return {
        "D": model["hidden_size"], "hd": hd, "H": held,
        "Hkv": held // group, "group": group,
        "o_fan_in": model["num_attention_heads"] * hd,
        "E": model["n_routed_experts"], "Eh": model["experts_held"],
        "e0": model.get("expert_offset", 0),
        "K": model["num_experts_per_tok"],
        "F": model["moe_intermediate_size"], "V": model["vocab_rows"],
        "conv": model["short_conv_kernel_size"], "R": model["gate_rank"],
        "eps": model["rms_norm_eps"], "L": model["num_hidden_layers"],
        "gqa": set(model["gqa_layers"]),
    }


def layout(model: dict, shape) -> dict:
    """``{path: (shape, kind)}`` of every parameter tensor held here
    (``harness/data.py``). A projection back to the residual stream keeps
    the fan-in of the whole layer (all heads), since this chip's rows are
    a slice of that matrix; ``A_log`` and ``dt_bias`` start at 0 (stated
    constants: a decay of ``exp(-softplus(.))`` a position)."""
    z = sizes(model)
    D, hd, H, F = z["D"], z["hd"], z["H"], z["F"]
    out = {
        ("embed", "table"): ((z["V"], D), {"std": 1.0}),
        ("final_norm", "scale"): ((D,), "ones"),
        ("lm_head", "kernel"): ((D, z["V"]), "kernel"),
    }
    for layer in range(z["L"]):
        p = f"layer_{layer}"
        out[(p, "mixer_norm", "scale")] = ((D,), "ones")
        out[(p, "moe_norm", "scale")] = ((D,), "ones")
        back = {"fan_in": z["o_fan_in"]}
        if layer in z["gqa"]:
            for name, width in (("q", H * hd), ("k", z["Hkv"] * hd),
                                ("v", z["Hkv"] * hd), ("g", H * hd)):
                out[(p, "gqa", name)] = ((D, width), "kernel")
            out[(p, "gqa", "o")] = ((H * hd, D), back)
        else:
            for name in ("q", "k", "v"):
                out[(p, "kda", name)] = ((D, H * hd), "kernel")
                out[(p, "kda", "conv_" + name)] = (
                    (z["conv"], H * hd), "kernel")
            out[(p, "kda", "f_a")] = ((D, z["R"]), "kernel")
            out[(p, "kda", "f_b")] = ((z["R"], H * hd), "kernel")
            out[(p, "kda", "dt_bias")] = ((H * hd,), "zeros")
            out[(p, "kda", "A_log")] = ((H,), "zeros")
            out[(p, "kda", "b")] = ((D, H), "kernel")
            out[(p, "kda", "g_a")] = ((D, z["R"]), "kernel")
            out[(p, "kda", "g_b")] = ((z["R"], H * hd), "kernel")
            out[(p, "kda", "o_norm")] = ((hd,), "ones")
            out[(p, "kda", "o")] = ((H * hd, D), back)
        out[(p, "moe", "router")] = ((D, z["E"]), "kernel")
        out[(p, "moe", "gate")] = ((z["Eh"], D, F), {"fan_in": D})
        out[(p, "moe", "up")] = ((z["Eh"], D, F), {"fan_in": D})
        out[(p, "moe", "down")] = ((z["Eh"], F, D), {"fan_in": F})
        out[(p, "moe", "shared_gate")] = ((D, F), "kernel")
        out[(p, "moe", "shared_up")] = ((D, F), "kernel")
        out[(p, "moe", "shared_down")] = ((F, D), "kernel")
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
    """``S_t = (I - b_t k_t k_t^T) Diag(a_t) S_(t-1) + b_t k_t v_t^T``,
    ``o_t = S_t^T q_t``, ``S_0 = 0``, position by position. Inputs
    ``[B, T, H, d]``, ``beta`` ``[B, T, H]``; ``log_decay`` is ``log a``."""
    B, T, H, d = qh.shape
    ein = functools.partial(jnp.einsum, precision=precision)

    def step(S, inp):
        qt, kt, vt, gt, bt = inp
        S = S * jnp.exp(gt)[..., None]
        u = bt[..., None] * (vt - ein("bhkv,bhk->bhv", S, kt))
        S = S + ein("bhk,bhv->bhkv", kt, u)
        return S, ein("bhkv,bhk->bhv", S, qt)

    xs = tuple(
        jnp.moveaxis(a, 1, 0) for a in (qh, kh, vh, log_decay, beta)
    )
    S0 = jnp.zeros((B, H, d, vh.shape[-1]), jnp.float32)
    block = SCAN_BLOCK if T % SCAN_BLOCK == 0 else T

    @jax.checkpoint
    def run_block(S, blk):
        return jax.lax.scan(step, S, blk)

    xs = jax.tree_util.tree_map(
        lambda a: a.reshape((T // block, block) + a.shape[1:]), xs
    )
    _, out = jax.lax.scan(run_block, S0, xs)
    return jnp.moveaxis(out.reshape((T,) + out.shape[2:]), 0, 1)


def kda(model, p, x, dense, q, precision):
    z = sizes(model)
    B, T, _ = x.shape
    H, hd = z["H"], z["hd"]

    def heads(a):
        return a.reshape(B, T, H, hd)

    def short(name):
        return jax.nn.silu(causal_conv(dense(x, p[name]), p["conv_" + name]))

    qh = q(l2_norm(heads(short("q"))) / math.sqrt(hd))
    kh = q(l2_norm(heads(short("k"))))
    vh = q(heads(short("v")))
    dt = jax.nn.softplus(dense(dense(x, p["f_a"]), p["f_b"]) + p["dt_bias"])
    log_decay = -jnp.exp(p["A_log"])[:, None] * heads(dt)
    beta = 2.0 * jax.nn.sigmoid(dense(x, p["b"]))
    o = delta_rule(qh, kh, vh, log_decay, beta, precision)
    o = rms_norm(o, p["o_norm"], z["eps"])
    gate = jax.nn.sigmoid(dense(dense(x, p["g_a"]), p["g_b"]))
    return dense(o.reshape(B, T, H * hd) * gate, p["o"])


def gqa(model, p, x, dense, q, precision):
    z = sizes(model)
    B, T, _ = x.shape
    hd, Hkv, group = z["hd"], z["Hkv"], z["group"]
    qh = dense(x, p["q"]).reshape(B, T, Hkv, group, hd)
    kh = dense(x, p["k"]).reshape(B, T, Hkv, hd)
    vh = dense(x, p["v"]).reshape(B, T, Hkv, hd)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def one_sequence(args):
        qs, ks, vs = args
        s = q(jnp.einsum("tkgd,skd->kgts", q(qs), q(ks),
                         precision=precision)) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return q(jnp.einsum("kgts,skd->tkgd", q(w), q(vs),
                            precision=precision))

    o = jax.lax.map(one_sequence, (qh, kh, vh))
    gate = jax.nn.sigmoid(dense(x, p["g"]))
    return dense(o.reshape(B, T, z["H"] * hd) * gate, p["o"])


def route(model, p, x, dense):
    """Scores over every expert of the layer, the chosen ones' indices and
    their normalised weights."""
    z = sizes(model)
    scores = jax.nn.sigmoid(dense(x, p["router"]))
    top, index = jax.lax.top_k(scores, z["K"])
    return index, top / jnp.sum(top, -1, keepdims=True)


def moe(model, p, x, dense, q, precision, shared: bool = True):
    z = sizes(model)
    index, weight = route(model, p, x, dense)

    def expert(gate, up, down):
        return dense(jax.nn.silu(dense(x, gate)) * dense(x, up), down)

    if shared:
        out = expert(p["shared_gate"], p["shared_up"], p["shared_down"])
    else:
        out = jnp.zeros_like(x)

    def add_expert(out, held):
        # Every token through this expert, its routing weight (0 for a
        # token that did not choose it) as the mask.
        e, gate, up, down = held
        mine = jnp.sum(weight * (index == z["e0"] + e), -1)
        return out + mine[..., None] * expert(gate, up, down), None

    out, _ = jax.lax.scan(
        add_expert, out,
        (jnp.arange(z["Eh"]),
         *(p[name][:z["Eh"]] for name in ("gate", "up", "down"))),
    )
    return out


def forward(model, params, tokens, dense, q, precision, **_):
    """Token ids ``[B, T]`` -> logits ``[B, T, vocab_rows]``."""
    z = sizes(model)
    kw = {"dense": dense, "q": q, "precision": precision}

    def block(layer, lp, h):
        x = rms_norm(h, lp["mixer_norm"]["scale"], z["eps"])
        if layer in z["gqa"]:
            h = h + gqa(model, lp["gqa"], x, **kw)
        else:
            h = h + kda(model, lp["kda"], x, **kw)
        x = rms_norm(h, lp["moe_norm"]["scale"], z["eps"])
        return h + moe(model, lp["moe"], x, **kw)

    h = params["embed"]["table"][tokens]
    for layer in range(z["L"]):
        h = jax.checkpoint(functools.partial(block, layer))(
            params[f"layer_{layer}"], h
        )
    x = rms_norm(h, params["final_norm"]["scale"], z["eps"])
    return dense(x, params["lm_head"]["kernel"])
