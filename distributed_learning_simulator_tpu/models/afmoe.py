"""AFMoE block stack (Trinity-Mini), as one chip of several that share
each layer.

Architecture (https://huggingface.co/arcee-ai/Trinity-Mini ``config.json``,
``model_type`` ``afmoe``; what the config has no key for follows the
family's public modelling code, transformers ``models/afmoe``): the
embedding times ``sqrt(hidden_size)``; every layer ``h <- h + N2(Attn(N1
h))``, ``h <- h + N4(MLP(N3 h))``, four RMS norms with a learned scale;
grouped-query softmax attention with a per-head RMS norm on ``q`` and
``k`` and a sigmoid gate per output channel before the output projection;
on ``sliding_attention`` layers rotary positions and a window (query ``i``
sees key ``j`` iff ``0 <= i - j < sliding_window``), on ``full_attention``
layers no positions and every key at or before the query; the first
``num_dense_layers`` layers a SwiGLU, the others sigmoid-routed experts
(the ``num_experts_per_tok`` largest of score + an untrained selection
bias; the chosen scores normalised and scaled by ``route_scale``) plus one
shared expert; untied embedding and head.

The module is told its share (``model-configs`` guide § 4):
``experts_held`` of ``num_experts`` starting at ``expert_offset`` and
``vocab_rows`` of the vocabulary; attention, the norms, the router, the
shared expert and the dense layers are whole. The router scores every
expert of the layer; tokens routed to experts held elsewhere cost no
expert FLOPs here and what those experts would add is left out. No code
stands in for the other chips.

``__call__`` returns ``(head, counts)`` as ``models/solar_open2.py`` does:
the head's kernel and input with the logits not yet made
(:class:`lm_parts.UnmadeLogits`), and the routing counters of the batch,
one entry an EXPERT layer.

Each part is a function of this file around the shared implementation
(``models/lm_parts.py``) and carries a ``jax.named_scope`` (``swa``,
``attn_full``, ``mlp_dense``, ``moe/route``, ``moe/experts``,
``moe/shared``, ``lm_head``): a device trace names an op by the source line
it came from, and :func:`scope_of_line` maps a line of this file to its
scope.
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_learning_simulator_tpu.models import lm_parts as parts

SLIDING, FULL = "sliding_attention", "full_attention"
#: Standard deviation the untrained selection bias is drawn with.
SELECTION_BIAS_STD = 0.01


def _attention(c) -> dict:
    return dict(heads=c.num_attention_heads, kv_heads=c.num_key_value_heads,
                head_dim=c.head_dim, dtype=jnp.dtype(c.dtype),
                query_block=c.query_block, qk_norm_eps=c.rms_norm_eps)


def swa(p, x, c):
    """A sliding layer's attention, projections to output: rotary
    positions, and a query reads only the keys of its band (the fused
    kernel on a TPU at shapes it takes, else
    :func:`lm_parts.banded_attention`: :func:`lm_parts.attention_core`)."""
    with jax.named_scope("swa"):
        return parts.gated_attention(
            p, x, window=c.sliding_window, rope_theta=c.rope_theta,
            **_attention(c))


def attn_full(p, x, c):
    """A full layer's attention, no positions: Solar-Open2's GQA layer
    with a norm on ``q`` and ``k`` (the fused kernel or
    :func:`lm_parts.causal_attention`: :func:`lm_parts.attention_core`)."""
    with jax.named_scope("attn_full"):
        return parts.gated_attention(p, x, **_attention(c))


def mlp_dense(p, x, *, dtype):
    with jax.named_scope("mlp_dense"):
        return parts.swiglu(x, p["gate"], p["up"], p["down"], dtype=dtype)


def moe_route(p, x, c):
    """The combine weight of each held expert for each token ``[N,
    held]`` (:func:`lm_parts.route`): the ``num_experts_per_tok`` largest
    of sigmoid score + selection bias, the chosen scores over their sum +
    1e-20 (``route_norm``) times ``route_scale``."""
    with jax.named_scope("moe/route"):
        return parts.route(
            p["router"], x, top_k=c.num_experts_per_tok,
            expert_offset=c.expert_offset, experts_held=c.experts_held,
            bias=p["bias"], eps=1e-20, scale=c.route_scale)


def moe_experts(p, x, combine, *, capacity: int, dtype):
    with jax.named_scope("moe/experts"):
        return parts.experts(p, x, combine, capacity=capacity, dtype=dtype)


def moe_shared(p, x, *, dtype):
    with jax.named_scope("moe/shared"):
        return parts.swiglu(x, p["shared_gate"], p["shared_up"],
                            p["shared_down"], dtype=dtype)


def lm_head(kernel, x, *, dtype):
    with jax.named_scope("lm_head"):
        return parts.lm_head(kernel, x, dtype=dtype)


def head_nll(kernel, x, targets, weight, *, dtype):
    with jax.named_scope("lm_head"):
        return parts.head_nll(kernel, x, targets, weight, dtype=dtype)


_SCOPES = {
    "swa": (swa,),
    "attn_full": (attn_full,),
    "mlp_dense": (mlp_dense,),
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

    hidden_size: int = 2048
    num_hidden_layers: int = 5
    num_dense_layers: int = 1
    layer_types: tuple = (SLIDING, SLIDING, SLIDING, SLIDING, FULL)
    head_dim: int = 128
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    intermediate_size: int = 6144
    num_experts: int = 128
    experts_held: int = 16
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1024
    route_scale: float = 2.826
    rms_norm_eps: float = 1e-5
    #: Queries a block of the attention's scores in the XLA form, which is
    #: all it sizes (the fused kernel that takes these layers on a TPU has
    #: blocks of its own, ``lm_parts.FUSED_BLOCKS``). 128, not
    #: Solar-Open2's 512: at 8,192 positions a sliding layer's forward and
    #: backward took 32.7 ms for 54.6 and the full layer's 126 for 146 in
    #: that form (one v5e, this layer alone; PERF.md § 6, PR 33).
    query_block: int = 128
    dtype: str = "bfloat16"

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers or set(
                self.layer_types) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types={self.layer_types!r}: one of {SLIDING!r}, "
                f"{FULL!r} for each of {self.num_hidden_layers} layers"
            )
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not make whole "
                f"groups over {self.num_key_value_heads} key/value heads"
            )


class _Block(nn.Module):
    cfg: Share
    sliding: bool
    dense: bool

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        D, hd = c.hidden_size, c.head_dim
        HD, KV = c.num_attention_heads * hd, c.num_key_value_heads * hd
        dtype = jnp.dtype(c.dtype)

        def norm(name):
            return parts.Norm(c.rms_norm_eps, name=name)

        p = parts.Params((
            ("q", (D, HD), D), ("k", (D, KV), D), ("v", (D, KV), D),
            ("g", (D, HD), D), ("o", (HD, D), HD),
            ("q_norm", (hd,), "ones"), ("k_norm", (hd,), "ones"),
        ), name="attn")()
        x = norm("attn_norm")(h)
        y = swa(p, x, c) if self.sliding else attn_full(p, x, c)
        h = h + norm("attn_post_norm")(y)
        x = norm("mlp_norm")(h)
        tokens = x.reshape(-1, D)
        if self.dense:
            F = c.intermediate_size
            p = parts.Params((
                ("gate", (D, F), D), ("up", (D, F), D), ("down", (F, D), F),
            ), name="mlp")()
            y, routed = mlp_dense(p, tokens, dtype=dtype), None
        else:
            F, Eh = c.moe_intermediate_size, c.experts_held
            p = parts.Params((
                ("router", (D, c.num_experts), D),
                # The selection bias: balanced in pretraining by a rule
                # of its own, never by a gradient (it has none).
                ("bias", (c.num_experts,), SELECTION_BIAS_STD ** -2),
                ("gate", (Eh, D, F), D), ("up", (Eh, D, F), D),
                ("down", (Eh, F, D), F),
                ("shared_gate", (D, F), D), ("shared_up", (D, F), D),
                ("shared_down", (F, D), F),
            ), name="moe")()
            combine = moe_route(p, tokens, c)
            y, load, overflow = moe_experts(
                p, tokens, combine, dtype=dtype,
                capacity=parts.expert_capacity(
                    tokens.shape[0], c.num_experts_per_tok, c.num_experts),
            )
            y, routed = y + moe_shared(p, tokens, dtype=dtype), (
                load, overflow)
        return h + norm("mlp_post_norm")(y.reshape(h.shape)), routed


class AFMoE(nn.Module):
    """``num_classes`` is the vocabulary this chip holds (what the
    dataset draws its ids from; ``vocab_rows``, if given, must agree);
    ``share`` says what else is held: build with :func:`afmoe`."""

    num_classes: int
    share: Share = Share()
    vocab_rows: int | None = None
    #: models/registry.init_params: draw the weights in one program,
    #: traced over this many positions.
    jit_init = True
    init_positions = 64
    #: The head makes its loss and both its gradients itself and hands
    #: on no logits (lm_parts.head_nll); the recorder's counter.
    head_backward_tied = True

    @property
    def attention_window(self) -> int:
        """The recorder's counter of the same name: the window of the
        banded layers, 0 where no layer is windowed."""
        return self.share.sliding_window if SLIDING in (
            self.share.layer_types) else 0

    def swa_keys_per_query_block(self, positions: int) -> int:
        """The recorder's counter: keys a block of queries reads on a
        banded layer at sequences of ``positions`` IN THE XLA FORM
        (``positions`` itself says every key is read: the band did not
        run). Where the fused kernel takes the layer
        (:meth:`fused_attention_layers`) this is what the XLA band would
        read; the kernel's blocks are ``lm_parts.FUSED_BLOCKS``."""
        if not self.attention_window:
            return 0
        return parts.band_keys(positions, self.share.sliding_window,
                               self.share.query_block)

    def fused_attention_layers(self, positions: int) -> int:
        """Layers whose attention core is the fused kernel where the
        program is lowered for a TPU, at sequences of ``positions``
        (:func:`lm_parts.fused_attention_applies`: by shapes, so all the
        layers or none). The recorder's counter of the same name is this
        on a TPU and 0 anywhere else."""
        c = self.share
        return c.num_hidden_layers * parts.fused_attention_applies(
            positions, c.head_dim, c.dtype)

    def _layers(self) -> tuple:
        c = self.share
        return tuple(
            (f"layer_{layer}", (c, kind == SLIDING, layer < c.num_dense_layers))
            for layer, kind in enumerate(c.layer_types))

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
        table = parts.Params(
            (("table", (vocab, D), D),), name="embed")()["table"]
        h = jnp.take(table, tokens, axis=0).astype(
            jnp.float32) * math.sqrt(D)
        loads, overflows = [], []
        for block in parts.blocks(self, _Block, tokens.shape):
            h, routed = block(h)
            if routed is not None:
                loads.append(routed[0])
                overflows.append(routed[1])
        x = parts.Norm(c.rms_norm_eps, name="final_norm")(h)
        kernel = parts.Params(
            (("kernel", (D, vocab), D),), name="lm_head")()["kernel"]
        head = parts.UnmadeLogits(kernel, x, jnp.dtype(c.dtype),
                                  (lm_head, head_nll))
        return head, parts.routing_counts(loads, overflows, tokens.size)


def afmoe(num_classes: int, vocab_rows: int | None = None, **share):
    """The registry's constructor: ``--model_args`` gives the share
    (``experts_held``, ``expert_offset``, ``vocab_rows``) and any
    published size a smaller preset changes, flat."""
    if "layer_types" in share:
        share["layer_types"] = tuple(share["layer_types"])
    return AFMoE(num_classes=num_classes, share=Share(**share),
                 vocab_rows=vocab_rows)
