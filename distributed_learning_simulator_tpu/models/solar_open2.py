"""Solar-Open2 block stack, as one chip of several that share each layer.

Architecture (https://huggingface.co/upstage/Solar-Open2-250B
``config.json``): RMSNorm pre-norm blocks; every ``gqa_interval + 1``-th
layer mixes by gated softmax attention without positions (GQA), the others
by the gated delta rule with a per-channel decay (KDA, Kimi Linear,
arXiv:2510.26692); every layer has sigmoid-routed experts (top-k, chosen
scores normalised) and one shared expert; untied embedding and head.

The module is told its share (``model-configs`` guide § 4): ``heads_held``
of ``num_attention_heads`` (with their key/value heads), ``experts_held``
of ``n_routed_experts`` starting at ``expert_offset``, ``vocab_rows`` of
the vocabulary. The router scores every expert of the layer; tokens routed
to experts held elsewhere cost no expert FLOPs here and what those experts
(and the absent heads) would add is left out. No code stands in for the
other chips.

``__call__`` returns ``(head, counts)``: ``head`` is the head's kernel and
input, the logits not yet made (:class:`UnmadeLogits`: the loss and its
gradient are made inside the head, :func:`head_nll`); ``counts`` are the
routing counters of the batch (``moe_local_assignments``, ``moe_routed_tokens``,
``moe_overflows`` per layer, ``moe_expert_load`` per layer and held
expert), which the engine sums into the round's aux outputs.

Each part is a function of this file and carries a ``jax.named_scope``
(``kda``, ``gqa``, ``moe/route``, ``moe/experts``, ``moe/shared``,
``lm_head``): a device trace names an op by the source line it came from,
and :func:`scope_of_line` maps a line of this file to its scope.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_learning_simulator_tpu.models import lm_parts as parts
from distributed_learning_simulator_tpu.models.traced_helpers import (
    causal_conv as _causal_conv,
    l2_norm as _l2_norm,
    mm as _mm,
    rms_norm as _rms_norm,
)

# --- the gated delta rule with a decay per key channel ----------------------


def chunked_delta_rule(q, k, v, log_decay, beta, dtype=jnp.bfloat16):
    """The delta rule with ``log_decay`` ``[B, T, H, d]``, a decay per key
    channel (:func:`lm_parts.chunked_delta_rule`, shared with
    ``models/olmo_hybrid.py``, chooses its form by that shape). A function
    of this file in scope ``kda``: the benchmark's scope test
    (``benchmark/tests/test_solar_open2_cell.py``) reads a line of the
    scope by this name."""
    return parts.chunked_delta_rule(q, k, v, log_decay, beta, dtype)


def kda_mixer(p, x, *, heads: int, head_dim: int, eps: float, dtype):
    """The gated delta-rule mixer over the heads held here; ``x`` is the
    normed input ``[B, T, D]``."""
    with jax.named_scope("kda"):
        B, T, _ = x.shape

        def split(a):
            return a.reshape(B, T, heads, head_dim)

        def short(name):
            return jax.nn.silu(
                _causal_conv(_mm(x, p[name], dtype), p["conv_" + name])
            )

        q = _l2_norm(split(short("q"))) / math.sqrt(head_dim)
        k = _l2_norm(split(short("k")))
        v = split(short("v"))
        dt = jax.nn.softplus(
            _mm(_mm(x, p["f_a"], dtype), p["f_b"], dtype)
            + p["dt_bias"].astype(jnp.float32)
        )
        log_decay = -jnp.exp(
            p["A_log"].astype(jnp.float32)
        )[:, None] * split(dt)
        beta = 2.0 * jax.nn.sigmoid(_mm(x, p["b"], dtype))
        o = chunked_delta_rule(q, k, v, log_decay, beta, dtype)
        o = _rms_norm(o, p["o_norm"], eps)
        gate = jax.nn.sigmoid(_mm(_mm(x, p["g_a"], dtype), p["g_b"], dtype))
        return _mm(o.reshape(B, T, heads * head_dim) * gate, p["o"], dtype)


def gqa_mixer(p, x, *, heads: int, kv_heads: int, head_dim: int, dtype,
              query_block: int = parts.QUERY_BLOCK):
    """Causal softmax attention without positions over the query heads
    held here and their key/value heads, gated per output channel."""
    with jax.named_scope("gqa"):
        return parts.gated_attention(
            p, x, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
            dtype=dtype, query_block=query_block)


# Each of the functions below is the scope's own line around the shared
# implementation (models/lm_parts.py, which is not user code to JAX): a
# device trace gives the ops made there THIS file's line.


def moe_route(p, x, *, top_k: int, expert_offset: int, experts_held: int):
    """Sigmoid scores over every expert of the layer, the ``top_k``
    largest, normalised: the combine weight of each held expert for each
    token ``[N, held]`` (:func:`lm_parts.route`)."""
    with jax.named_scope("moe/route"):
        return parts.route(p["router"], x, top_k=top_k,
                           expert_offset=expert_offset,
                           experts_held=experts_held)


def moe_experts(p, x, combine, *, capacity: int, dtype):
    """The held experts' part of the layer for the tokens routed to them
    (:func:`lm_parts.experts`, shared with ``models/afmoe.py``).

    Each held expert gathers the tokens that chose it into ``capacity``
    slots and the experts run as one grouped product over ``[held,
    capacity]``: the work follows the tokens routed here, not the tokens
    in the batch. No token is dropped: when any held expert is chosen by
    more than ``capacity`` tokens the layer computes every held expert
    over every token with the combine weights as a mask (``lax.cond``;
    counted in ``overflow``).

    ``p`` holds ``gate``, ``up`` ``[held, D, F]`` and ``down`` ``[held,
    F, D]``; ``x`` ``[N, D]`` the normed tokens; ``combine`` ``[N,
    held]`` the weights :func:`moe_route` gave (0 where a token did not
    choose the expert). Returns ``(y [N, D] f32, load [held] int32: the
    assignments that fell to each held expert, overflow: whether the
    fallback ran)``.

    The body is one line on purpose: the implementation is registered
    as not being user code, so every op it makes carries THIS function's
    line in a device trace, and ``scope_of_line`` gives it to
    ``moe/experts``.
    """
    with jax.named_scope("moe/experts"):
        return parts.experts(p, x, combine, capacity=capacity, dtype=dtype)


def moe_shared(p, x, *, dtype):
    with jax.named_scope("moe/shared"):
        return parts.swiglu(x, p["shared_gate"], p["shared_up"],
                            p["shared_down"], dtype=dtype)


def lm_head(kernel, x, *, dtype):
    """The logits themselves (:func:`lm_parts.lm_head`); the model does
    not hand them to the engine: :func:`head_nll`."""
    with jax.named_scope("lm_head"):
        return parts.lm_head(kernel, x, dtype=dtype)


def head_nll(kernel, x, targets, weight, *, dtype):
    """The head's loss and both its gradients, made in the head
    (:func:`lm_parts.head_nll`, which says why): no logits leave it."""
    with jax.named_scope("lm_head"):
        return parts.head_nll(kernel, x, targets, weight, dtype=dtype)


#: What the model hands on in place of logits, with this file's head.
UnmadeLogits = functools.partial(parts.UnmadeLogits,
                                 head=(lm_head, head_nll))

_SCOPES = {
    "kda": (chunked_delta_rule, kda_mixer),
    "gqa": (gqa_mixer,),
    "moe/route": (moe_route,),
    "moe/experts": (moe_experts,),
    "moe/shared": (moe_shared,),
    "lm_head": (lm_head, head_nll),
}

#: The named scope whose code holds a source line of this file.
scope_of_line = parts.scope_lookup(_SCOPES)


# --- the module -------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Share:
    """The published sizes and what of a layer is held here."""

    hidden_size: int = 4096
    num_hidden_layers: int = 4
    gqa_layers: tuple = (0,)
    head_dim: int = 128
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    heads_held: int = 8
    n_routed_experts: int = 320
    experts_held: int = 8
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1280
    short_conv_kernel_size: int = 4
    gate_rank: int = 128
    rms_norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    @property
    def kv_heads_held(self) -> int:
        group = self.num_attention_heads // self.num_key_value_heads
        if self.heads_held % group:
            raise ValueError(
                f"heads_held={self.heads_held} must be whole groups of "
                f"{group} query heads a key/value head"
            )
        return self.heads_held // group

    def expert_capacity(self, n_tokens: int) -> int:
        return parts.expert_capacity(
            n_tokens, self.num_experts_per_tok, self.n_routed_experts)


class _Block(nn.Module):
    cfg: Share
    is_gqa: bool

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        D, hd, H = c.hidden_size, c.head_dim, c.heads_held
        F = c.moe_intermediate_size
        dtype = jnp.dtype(c.dtype)
        back = c.num_attention_heads * hd
        x = parts.Norm(c.rms_norm_eps, name="mixer_norm")(h)
        if self.is_gqa:
            kv = c.kv_heads_held
            p = parts.Params((
                ("q", (D, H * hd), D), ("k", (D, kv * hd), D),
                ("v", (D, kv * hd), D), ("g", (D, H * hd), D),
                ("o", (H * hd, D), back),
            ), name="gqa")()
            h = h + gqa_mixer(p, x, heads=H, kv_heads=kv, head_dim=hd,
                              dtype=dtype)
        else:
            R, taps = c.gate_rank, c.short_conv_kernel_size
            p = parts.Params((
                ("q", (D, H * hd), D), ("k", (D, H * hd), D),
                ("v", (D, H * hd), D),
                ("conv_q", (taps, H * hd), taps),
                ("conv_k", (taps, H * hd), taps),
                ("conv_v", (taps, H * hd), taps),
                ("f_a", (D, R), D), ("f_b", (R, H * hd), R),
                ("dt_bias", (H * hd,), "zeros"), ("A_log", (H,), "zeros"),
                ("b", (D, H), D), ("g_a", (D, R), D),
                ("g_b", (R, H * hd), R), ("o_norm", (hd,), "ones"),
                ("o", (H * hd, D), back),
            ), name="kda")()
            h = h + kda_mixer(p, x, heads=H, head_dim=hd,
                              eps=c.rms_norm_eps, dtype=dtype)
        x = parts.Norm(c.rms_norm_eps, name="moe_norm")(h)
        Eh = c.experts_held
        p = parts.Params((
            ("router", (D, c.n_routed_experts), D),
            ("gate", (Eh, D, F), D), ("up", (Eh, D, F), D),
            ("down", (Eh, F, D), F),
            ("shared_gate", (D, F), D), ("shared_up", (D, F), D),
            ("shared_down", (F, D), F),
        ), name="moe")()
        tokens = x.reshape(-1, D)
        combine = moe_route(
            p, tokens, top_k=c.num_experts_per_tok,
            expert_offset=c.expert_offset, experts_held=Eh,
        )
        routed, load, overflow = moe_experts(
            p, tokens, combine, capacity=c.expert_capacity(tokens.shape[0]),
            dtype=dtype,
        )
        y = routed + moe_shared(p, tokens, dtype=dtype)
        return h + y.reshape(h.shape), (load, overflow)


class SolarOpen2(nn.Module):
    """``num_classes`` is the vocabulary this chip holds (what the
    dataset draws its ids from; ``vocab_rows``, if given, must agree);
    ``share`` says what else is held: build with :func:`solar_open2`."""

    num_classes: int
    share: Share = Share()
    vocab_rows: int | None = None
    #: models/registry.init_params: draw the weights in one program,
    #: traced over this many positions.
    jit_init = True
    init_positions = parts.DELTA_CHUNK
    #: The head makes its loss and both its gradients itself and hands
    #: on no logits (head_nll); the recorder's counter of the same name.
    head_backward_tied = True

    @property
    def delta_rule_decay_width(self) -> int:
        """The recorder's counter of the same name: decays a head and
        position the delta rule is given (one per key channel here), 0
        where no layer mixes by it."""
        c = self.share
        return c.head_dim if set(range(c.num_hidden_layers)) - set(
            c.gqa_layers) else 0

    def fused_attention_layers(self, positions: int) -> int:
        """GQA layers whose attention core is the fused kernel where the
        program is lowered for a TPU, at sequences of ``positions``
        (:func:`lm_parts.fused_attention_applies`); the recorder's
        counter of the same name is this on a TPU and 0 anywhere else."""
        c = self.share
        layers = set(c.gqa_layers) & set(range(c.num_hidden_layers))
        return len(layers) * parts.fused_attention_applies(
            positions, c.head_dim, c.dtype)

    def _layers(self) -> tuple:
        c = self.share
        return tuple((f"layer_{layer}", (c, layer in c.gqa_layers))
                     for layer in range(c.num_hidden_layers))

    def remat_blocks(self, params, tokens_shape) -> int:
        """Leading blocks wrapped in ``nn.remat`` at a step of
        ``tokens_shape`` (:func:`lm_parts.rematerialised_blocks`); the
        recorder's counter of the same name."""
        return parts.rematerialised_blocks(
            _Block, self._layers(), params, tokens_shape, self.share)

    @nn.compact
    def __call__(self, tokens):
        if self.vocab_rows not in (None, self.num_classes):
            raise ValueError(
                f"vocab_rows={self.vocab_rows} but the dataset draws its "
                f"ids from {self.num_classes}"
            )
        c = self.share
        D, vocab = c.hidden_size, self.num_classes
        table = parts.Params((("table", (vocab, D), 1),), name="embed")()["table"]
        h = jnp.take(table, tokens, axis=0).astype(jnp.float32)
        loads, overflows = [], []
        for block in parts.blocks(self, _Block, tokens.shape):
            h, (load, overflow) = block(h)
            loads.append(load)
            overflows.append(overflow)
        x = parts.Norm(c.rms_norm_eps, name="final_norm")(h)
        kernel = parts.Params(
            (("kernel", (D, vocab), D),), name="lm_head")()["kernel"]
        head = UnmadeLogits(kernel, x, jnp.dtype(c.dtype))
        return head, parts.routing_counts(loads, overflows, tokens.size)


def solar_open2(num_classes: int, vocab_rows: int | None = None, **share):
    """The registry's constructor: ``--model_args`` gives the share
    (``heads_held``, ``experts_held``, ``vocab_rows``, ...) and any
    published size a smaller preset changes, flat."""
    if "gqa_layers" in share:
        share["gqa_layers"] = tuple(share["gqa_layers"])
    return SolarOpen2(num_classes=num_classes, share=Share(**share),
                      vocab_rows=vocab_rows)
