"""Olmo-Hybrid block stack (Olmo-Hybrid-7B), one pipeline stage of a
deployment that holds a period of layers a chip.

Architecture (https://huggingface.co/allenai/Olmo-Hybrid-7B ``config.json``,
``model_type`` ``olmo_hybrid``; what the config has no key for follows the
family's public modelling code, transformers ``models/olmo3`` for the
blocks and ``models/qwen3_next`` for the Gated DeltaNet layer): a dense
stack with NO pre-norm; every layer ``h <- h + N1(Mixer(h))``, ``h <- h +
N2(MLP(h))``, each norm an RMS norm with a learned scale; ``layer_types``
``linear_attention`` mixes by the gated delta rule with ONE decay a head
and position (Gated DeltaNet: ``q, k, v`` through a short causal
convolution and SiLU, ``q, k`` l2-normed, ``q`` over ``sqrt(d_k)``; ``g =
-exp(A_log) softplus(W_a x + dt_bias)``; ``beta = 2 sigmoid(W_b x)``
(``linear_allow_neg_eigval``); keys of ``linear_key_head_dim``, values of
``linear_value_head_dim``; the output RMS-normed per head with one scale
shared by the heads, times ``SiLU(W_g x)``), ``full_attention`` by causal
softmax attention over as many key/value heads as query heads, no gate, no
positions (``rope_theta`` null), an RMS norm over the WHOLE ``q`` and ``k``
projections (Olmo 3's); a SwiGLU MLP in every layer; final RMS norm;
untied embedding and head.

The module is told its share (``model-configs`` guide § 4): ``vocab_rows``
of the vocabulary (embedding and head vocabulary-parallel over the stages);
the layers it holds are whole. No code stands in for the other chips.

``__call__`` returns the head's kernel and input with the logits not yet
made (:class:`lm_parts.UnmadeLogits`): the loss and both its gradients are
made inside the head. A dense model has no counters.

Each part is a function of this file around the shared implementation
(``models/lm_parts.py``) and carries a ``jax.named_scope`` (``gdn``,
``attn_full``, ``mlp``, ``lm_head``): a device trace names an op by the
source line it came from, and :func:`scope_of_line` maps a line of this
file to its scope.
"""

from __future__ import annotations

import dataclasses
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

LINEAR, FULL = "linear_attention", "full_attention"


def gdn(p, x, c):
    """A linear layer's mixer, projections to output: Gated DeltaNet over
    ``linear_num_key_heads`` heads, the delta rule with one decay a head
    (:func:`lm_parts.chunked_delta_rule`, chosen by the decay's shape)."""
    with jax.named_scope("gdn"):
        B, T, _ = x.shape
        H, dk, dv = c.linear_num_key_heads, c.linear_key_head_dim, (
            c.linear_value_head_dim)
        dtype = jnp.dtype(c.dtype)

        def short(name, width):
            return jax.nn.silu(_causal_conv(
                _mm(x, p[name], dtype), p["conv_" + name])).reshape(
                    B, T, H, width)

        q = _l2_norm(short("q", dk)) / math.sqrt(dk)
        k = _l2_norm(short("k", dk))
        v = short("v", dv)
        log_decay = -jnp.exp(p["A_log"].astype(jnp.float32)) * (
            jax.nn.softplus(_mm(x, p["a"], dtype)
                            + p["dt_bias"].astype(jnp.float32)))
        beta = 2.0 * jax.nn.sigmoid(_mm(x, p["b"], dtype))
        o = parts.chunked_delta_rule(q, k, v, log_decay, beta, dtype)
        o = _rms_norm(o, p["o_norm"], c.rms_norm_eps) * jax.nn.silu(
            _mm(x, p["g"], dtype)).reshape(B, T, H, dv)
        return _mm(o.reshape(B, T, H * dv), p["o"], dtype)


def attn_full(p, x, c):
    """A full layer's attention, projections to output: no positions, no
    gate, q and k normed over the whole projection (the fused kernel or
    :func:`lm_parts.causal_attention`: :func:`lm_parts.attention_core`)."""
    with jax.named_scope("attn_full"):
        return parts.gated_attention(
            p, x, heads=c.num_attention_heads,
            kv_heads=c.num_key_value_heads, head_dim=c.head_dim,
            dtype=jnp.dtype(c.dtype), qk_norm_eps=c.rms_norm_eps,
            qk_norm="projection")


def mlp(p, x, *, dtype):
    with jax.named_scope("mlp"):
        return parts.swiglu(x, p["gate"], p["up"], p["down"], dtype=dtype)


def lm_head(kernel, x, *, dtype):
    with jax.named_scope("lm_head"):
        return parts.lm_head(kernel, x, dtype=dtype)


def head_nll(kernel, x, targets, weight, *, dtype):
    with jax.named_scope("lm_head"):
        return parts.head_nll(kernel, x, targets, weight, dtype=dtype)


_SCOPES = {
    "gdn": (gdn,),
    "attn_full": (attn_full,),
    "mlp": (mlp,),
    "lm_head": (lm_head, head_nll),
}
#: The named scope whose code holds a source line of this file.
scope_of_line = parts.scope_lookup(_SCOPES)


# --- the module -------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Share:
    """The published sizes; the layers held are whole."""

    hidden_size: int = 3840
    num_hidden_layers: int = 4
    layer_types: tuple = (LINEAR, LINEAR, LINEAR, FULL)
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    intermediate_size: int = 11008
    rms_norm_eps: float = 1e-6
    dtype: str = "bfloat16"

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers or set(
                self.layer_types) - {LINEAR, FULL}:
            raise ValueError(
                f"layer_types={self.layer_types!r}: one of {LINEAR!r}, "
                f"{FULL!r} for each of {self.num_hidden_layers} layers"
            )
        if self.linear_num_value_heads != self.linear_num_key_heads:
            raise ValueError(
                f"{self.linear_num_value_heads} value heads against "
                f"{self.linear_num_key_heads} key heads: the delta rule "
                "here takes a key head a value head"
            )
        if self.hidden_size % self.num_attention_heads or (
                self.num_attention_heads % self.num_key_value_heads):
            raise ValueError(
                f"{self.num_attention_heads} heads over hidden "
                f"{self.hidden_size} and {self.num_key_value_heads} "
                "key/value heads: no whole head size or groups"
            )

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


class _Block(nn.Module):
    cfg: Share
    linear: bool

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        D, F = c.hidden_size, c.intermediate_size
        dtype = jnp.dtype(c.dtype)
        if self.linear:
            H, taps = c.linear_num_key_heads, c.linear_conv_kernel_dim
            K, V = H * c.linear_key_head_dim, H * c.linear_value_head_dim
            p = parts.Params((
                ("q", (D, K), D), ("k", (D, K), D), ("v", (D, V), D),
                ("conv_q", (taps, K), taps), ("conv_k", (taps, K), taps),
                ("conv_v", (taps, V), taps),
                ("a", (D, H), D), ("b", (D, H), D),
                ("dt_bias", (H,), "zeros"), ("A_log", (H,), "zeros"),
                ("g", (D, V), D), ("o_norm", (c.linear_value_head_dim,),
                                   "ones"),
                ("o", (V, D), V),
            ), name="gdn")()
            y = gdn(p, h, c)
        else:
            HD = c.num_attention_heads * c.head_dim
            KV = c.num_key_value_heads * c.head_dim
            p = parts.Params((
                ("q", (D, HD), D), ("k", (D, KV), D), ("v", (D, KV), D),
                ("o", (HD, D), HD),
                ("q_norm", (HD,), "ones"), ("k_norm", (KV,), "ones"),
            ), name="attn")()
            y = attn_full(p, h, c)
        h = h + parts.Norm(c.rms_norm_eps, name="attn_post_norm")(y)
        p = parts.Params((
            ("gate", (D, F), D), ("up", (D, F), D), ("down", (F, D), F),
        ), name="mlp")()
        y = mlp(p, h, dtype=dtype)
        return h + parts.Norm(c.rms_norm_eps, name="mlp_post_norm")(y)


class OlmoHybrid(nn.Module):
    """``num_classes`` is the vocabulary this chip holds (what the
    dataset draws its ids from; ``vocab_rows``, if given, must agree);
    ``share`` the published sizes: build with :func:`olmo_hybrid`."""

    num_classes: int
    share: Share = Share()
    vocab_rows: int | None = None
    #: models/registry.init_params: draw the weights in one program,
    #: traced over this many positions.
    jit_init = True
    init_positions = parts.DELTA_CHUNK
    #: The head makes its loss and both its gradients itself and hands
    #: on no logits (lm_parts.head_nll); the recorder's counter.
    head_backward_tied = True

    @property
    def delta_rule_decay_width(self) -> int:
        """The recorder's counter of the same name: decays a head and
        position the delta rule is given (one a head here), 0 where no
        layer mixes by it."""
        return int(LINEAR in self.share.layer_types)

    def fused_attention_layers(self, positions: int) -> int:
        """Full layers whose attention core is the fused kernel where the
        program is lowered for a TPU, at sequences of ``positions``
        (:func:`lm_parts.fused_attention_applies`); the recorder's counter
        of the same name is this on a TPU and 0 anywhere else."""
        c = self.share
        return c.layer_types.count(FULL) * parts.fused_attention_applies(
            positions, c.head_dim, c.dtype)

    def _layers(self) -> tuple:
        return tuple((f"layer_{layer}", (self.share, kind == LINEAR))
                     for layer, kind in enumerate(self.share.layer_types))

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
            (("table", (vocab, D), 1),), name="embed")()["table"]
        h = jnp.take(table, tokens, axis=0).astype(jnp.float32)
        for block in parts.blocks(self, _Block, tokens.shape):
            h = block(h)
        x = parts.Norm(c.rms_norm_eps, name="final_norm")(h)
        kernel = parts.Params(
            (("kernel", (D, vocab), D),), name="lm_head")()["kernel"]
        return parts.UnmadeLogits(kernel, x, jnp.dtype(c.dtype),
                                  (lm_head, head_nll))


def olmo_hybrid(num_classes: int, vocab_rows: int | None = None, **share):
    """The registry's constructor: ``--model_args`` gives ``vocab_rows``
    and any published size a smaller preset changes, flat."""
    if "layer_types" in share:
        share["layer_types"] = tuple(share["layer_types"])
    return OlmoHybrid(num_classes=num_classes, share=Share(**share),
                      vocab_rows=vocab_rows)
