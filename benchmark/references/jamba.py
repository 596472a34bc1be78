"""Plain reference: Jamba's block stack (AI21-Jamba2-3B), one chip's share
of a tensor-parallel deployment.

The published architecture (https://huggingface.co/ai21labs/AI21-Jamba2-3B
``config.json``, ``model_type`` ``jamba``; the layer kinds as transformers
``models/jamba/modeling_jamba.py`` writes them: ``JambaMambaMixer``'s
``slow_forward``, ``JambaAttention``, ``JambaMLP``): ``h = table[tokens]``;
every layer ``h <- h + Mixer(RMSNorm(h))``, ``h <- h + MLP(RMSNorm(h))``;
layer ``i`` is attention iff ``i % attn_layer_period == attn_layer_offset``,
else Mamba.

* Mamba, ``u`` the normed input: ``x = u W_x_in``, ``z = u W_z_in``; ``x <-
  SiLU(conv(x) + b_conv)``, a causal depthwise convolution of
  ``mamba_d_conv`` taps; ``[d, B, C] = x W_xproj`` split ``[R, N, N]``, each
  RMS-normed with its own scale; ``Delta = softplus(d W_dt + b_dt)``, ``A =
  -exp(A_log)``; per channel ``c`` and state ``n``: ``s_t[c, n] =
  exp(Delta_t[c] A[c, n]) s_(t-1)[c, n] + Delta_t[c] B_t[n] x_t[c]``, ``y_t[c]
  = sum_n s_t[c, n] C_t[n] + D[c] x_t[c]``; ``out = (y * SiLU(z)) W_out``.
* attention: query heads over one key/value head (or groups of them),
  causal softmax at ``1 / sqrt(head)``, no positions, no gate, no norm;
  ``W_o``.
* every layer: ``MLP(x) = W_down(SiLU(x W_gate) * x W_up)``.
* final RMS norm; logits ``x table^T`` (the head is the embedding, tied).

``model`` (the configuration's ``"model"`` object) says what is held here:
``channels_held`` Mamba channels, ``heads_held`` query heads,
``mlp_columns_held`` MLP columns and ``vocab_rows`` vocabulary rows, each
sublayer's output the partial sum those give; the k/v head and the three
inner norms whole. In the deployment the ranks' partial ``x W_xproj`` is
summed before the inner norms; here, as in the program, it is not: ``d``,
``B`` and ``C`` come from the held channels alone.

Departures from the published model, all stated in the configuration's
``reduced`` and ``assumed``: one period of 14 layers of 28, the share
above, no exchange between ranks; weights drawn from the seed, with
``A_log`` and ``b_dt`` as their shifts from Mamba's initial values
(``A_log = log n``; ``b_dt = softplus^-1`` of steps spread evenly in log
over ``[0.001, 0.1]`` across the held channels): the benchmark's layouts
draw normals and constants.

Everything is ``jax.numpy`` in float32. The scan is a ``lax.scan`` over
positions, one at a time, run in blocks under ``jax.checkpoint`` so that
its backward pass keeps one state a block; attention materialises its
scores a block of queries at a time against every key. Products are built
from what ``fed.primitives`` hands in (``dense``, ``q``, ``precision``).
Imports nothing of the program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

SCAN_BLOCK = 128  # positions per checkpointed block of the recurrence
QUERY_BLOCK = 256  # queries whose scores over every key are live at once
ATTENTION = "attention"


def sizes(model: dict) -> dict:
    D = model["hidden_size"]
    hd = D // model["num_attention_heads"]
    H = model["heads_held"]
    Hkv = model["num_key_value_heads"]
    kv = 1 if Hkv == 1 else H // (model["num_attention_heads"] // Hkv)
    return {
        "D": D, "hd": hd, "H": H, "Hkv": kv,
        "E": model["channels_held"], "N": model["mamba_d_state"],
        "R": model["mamba_dt_rank"], "K": model["mamba_d_conv"],
        "F": model["mlp_columns_held"], "V": model["vocab_rows"],
        "eps": model["rms_norm_eps"],
    }


def layer_kinds(model: dict) -> list[str]:
    period, offset = model["attn_layer_period"], model["attn_layer_offset"]
    return [ATTENTION if i % period == offset else "mamba"
            for i in range(model["num_hidden_layers"])]


def layout(model: dict, shape) -> dict:
    """``{path: (shape, kind)}`` of every parameter tensor held here
    (``harness/data.py``): kernels N(0, 1/fan-in) over the held fan-in,
    the embedding N(0, 1/hidden), norm scales and ``D`` 1, the shifts of
    ``A_log`` and ``b_dt`` and the convolution's bias 0."""
    z = sizes(model)
    D, E, N, R, F = z["D"], z["E"], z["N"], z["R"], z["F"]
    out = {
        ("embed", "table"): ((z["V"], D), {"std": 1.0 / math.sqrt(D)}),
        ("final_norm", "scale"): ((D,), "ones"),
    }
    for layer, kind in enumerate(layer_kinds(model)):
        p = f"layer_{layer}"
        out[(p, "input_norm", "scale")] = ((D,), "ones")
        out[(p, "ff_norm", "scale")] = ((D,), "ones")
        for name, dims in (("gate", (D, F)), ("up", (D, F)),
                           ("down", (F, D))):
            out[(p, "mlp", name)] = (dims, "kernel")
        if kind == ATTENTION:
            HD, KV = z["H"] * z["hd"], z["Hkv"] * z["hd"]
            for name, dims in (("q", (D, HD)), ("k", (D, KV)),
                               ("v", (D, KV)), ("o", (HD, D))):
                out[(p, "attn", name)] = (dims, "kernel")
            continue
        m = (p, "mamba")
        for name, dims in (("in_x", (D, E)), ("in_z", (D, E)),
                           ("conv", (z["K"], E)), ("x_proj", (E, R + 2 * N)),
                           ("dt_proj", (R, E)), ("out", (E, D))):
            out[m + (name,)] = (dims, "kernel")
        for name, dims in (("dt_norm", (R,)), ("b_norm", (N,)),
                           ("c_norm", (N,)), ("D", (E,))):
            out[m + (name,)] = (dims, "ones")
        for name, dims in (("conv_bias", (E,)), ("dt_bias_shift", (E,)),
                           ("A_log_shift", (E, N))):
            out[m + (name,)] = (dims, "zeros")
    return out


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def causal_conv(x, w, bias):
    """``y_t = bias + sum_j w[j] x_(t - (K-1) + j)``, zeros before the
    sequence; ``x`` ``[B, T, C]``, ``w`` ``[K, C]``."""
    taps = w.shape[0]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return bias + sum(padded[:, j:j + x.shape[1]] * w[j]
                      for j in range(taps))


def initial_a_log(E: int, N: int):
    return jnp.log(jnp.tile(jnp.arange(1.0, N + 1.0), (E, 1)))


def initial_dt_bias(E: int):
    """``softplus^-1(dt)`` for ``dt`` evenly spaced in log over ``[0.001,
    0.1]``, at the middle of each of ``E`` equal steps."""
    frac = (np.arange(E) + 0.5) / E
    dt = 0.001 * (0.1 / 0.001) ** frac
    return jnp.asarray(np.log(np.expm1(dt)), jnp.float32)


def scan(x, delta, A, B, C, D):
    """The selective scan position by position: ``x``, ``delta`` ``[B, T,
    E]``, ``A`` ``[E, N]``, ``B``, ``C`` ``[B, T, N]``, ``D`` ``[E]``."""
    batch, T, E = x.shape

    def step(s, inp):  # s [B, E, N]
        x_t, d_t, b_t, c_t = inp
        s = (jnp.exp(d_t[:, :, None] * A[None]) * s
             + d_t[:, :, None] * b_t[:, None, :] * x_t[:, :, None])
        return s, jnp.sum(s * c_t[:, None, :], axis=-1) + D * x_t

    block = SCAN_BLOCK if T % SCAN_BLOCK == 0 else T

    @jax.checkpoint
    def run_block(s, blk):
        return jax.lax.scan(step, s, blk)

    xs = tuple(
        jnp.moveaxis(a, 1, 0).reshape((T // block, block) + a.shape[:1]
                                      + a.shape[2:])
        for a in (x, delta, B, C))
    s0 = jnp.zeros((batch, E, A.shape[1]), jnp.float32)
    _, y = jax.lax.scan(run_block, s0, xs)
    return jnp.moveaxis(y.reshape((T, batch, E)), 0, 1)


def mamba(model, p, u, dense, q, precision):
    z = sizes(model)
    E, N, R = z["E"], z["N"], z["R"]
    x = jax.nn.silu(causal_conv(dense(u, p["in_x"]), p["conv"],
                                p["conv_bias"]))
    gate = dense(u, p["in_z"])
    xdbc = dense(x, p["x_proj"])
    d = rms_norm(xdbc[..., :R], p["dt_norm"], z["eps"])
    B = rms_norm(xdbc[..., R:R + N], p["b_norm"], z["eps"])
    C = rms_norm(xdbc[..., R + N:], p["c_norm"], z["eps"])
    delta = jax.nn.softplus(dense(d, p["dt_proj"]) + initial_dt_bias(E)
                            + p["dt_bias_shift"])
    A = -jnp.exp(initial_a_log(E, N) + p["A_log_shift"])
    y = q(scan(q(x), q(delta), A, q(B), q(C), p["D"]))
    return dense(y * jax.nn.silu(gate), p["out"])


def attention(model, p, u, dense, q, precision):
    z = sizes(model)
    B, T, _ = u.shape
    H, Hkv, hd = z["H"], z["Hkv"], z["hd"]
    qh = dense(u, p["q"]).reshape(B, T, H, hd)
    kh = dense(u, p["k"]).reshape(B, T, Hkv, hd)
    vh = dense(u, p["v"]).reshape(B, T, Hkv, hd)
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
    kinds = layer_kinds(model)

    def block(layer, lp, h):
        u = rms_norm(h, lp["input_norm"]["scale"], eps)
        if kinds[layer] == ATTENTION:
            h = h + attention(model, lp["attn"], u, **kw)
        else:
            h = h + mamba(model, lp["mamba"], u, **kw)
        return h + mlp(lp["mlp"], rms_norm(h, lp["ff_norm"]["scale"], eps),
                       dense)

    table = params["embed"]["table"]
    h = table[tokens]
    for layer in range(model["num_hidden_layers"]):
        h = jax.checkpoint(functools.partial(block, layer))(
            params[f"layer_{layer}"], h)
    x = rms_norm(h, params["final_norm"]["scale"], eps)
    return dense(x, table.T)
