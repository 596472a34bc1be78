"""Jamba block stack (AI21-Jamba2-3B), as one chip of several that share
each layer.

Architecture (https://huggingface.co/ai21labs/AI21-Jamba2-3B ``config.json``,
``model_type`` ``jamba``; the layer kinds as transformers
``models/jamba/modeling_jamba.py`` spells them out): a pre-norm stack, every
layer ``h <- h + Mixer(N1(h))``, ``h <- h + MLP(N2(h))``, RMS norms with a
learned scale. Layer ``i`` mixes by attention iff ``i % attn_layer_period
== attn_layer_offset``, else by a Mamba-1 mixer:

* Mamba (``JambaMambaMixer``), ``u`` the normed input, ``E = mamba_expand
  x hidden`` channels: ``x = u W_x_in``, ``z = u W_z_in``; ``x <-
  SiLU(conv4(x) + b_conv)`` (causal, depthwise); ``[delta, B, C] = x
  W_xproj``, each RMS-normed with a scale of its own; ``Delta =
  softplus(delta W_dt + b_dt)``, ``A = -exp(A_log)``; the selective scan
  (``ops/selective_scan.py``); ``out = (y * SiLU(z)) W_out``. No bias but
  the convolution's and ``b_dt``.
* attention: ``num_attention_heads`` query heads over ``num_key_value_heads``
  key/value heads (one: MQA), causal softmax at ``1 / sqrt(head)``, no
  positions, no gate, no q/k norm.
* a SwiGLU MLP in every layer (``num_experts`` 1: no expert layer).
* final RMS norm; the head is the embedding, tied.

The module is told its share (``model-configs`` guide § 4) of a deployment
that divides each layer over tensor-parallel ranks: ``channels_held`` of
the Mamba channels (their columns of ``W_x_in``, ``W_z_in``, convolution,
``W_dt``, ``b_dt``, ``A_log``, ``D`` and their rows of ``W_xproj`` and
``W_out``; the three inner norms whole), ``heads_held`` of the query heads
(the key/value head whole where there is one), ``mlp_columns_held`` of the
MLP, and ``vocab_rows`` of the vocabulary. Each sublayer's output is this
rank's partial sum. The mixer is two functions split where the deployment
exchanges ``x W_xproj`` (a ``[T, R + 2N]`` partial, all-reduced over the
ranks before the inner norms): :func:`mamba_in` and :func:`mamba_out`,
composed directly here. No code stands in for the other chips.

``A_log`` and ``b_dt`` are held as their change from Mamba's initial values
(``A_log = log n`` for state ``n = 1..N``; ``b_dt`` the inverse softplus of
steps spread log-uniformly over ``[0.001, 0.1]`` across the held channels):
parameters ``A_log_shift`` and ``dt_bias_shift``, zero at the start. SGD
moves a shift as it moves the value; the benchmark's weight layouts draw
normals and constants (``benchmark/harness/data.py``).

``__call__`` returns the head's kernel (the embedding's transpose) and
input with the logits not yet made (:class:`lm_parts.UnmadeLogits`). Each
part carries a ``jax.named_scope`` (``mamba``, ``ssm_scan``, ``attn_full``,
``mlp``, ``lm_head``), and :func:`scope_of_line` maps a line of this file to
its scope.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distributed_learning_simulator_tpu.models import lm_parts as parts
from distributed_learning_simulator_tpu.models.traced_helpers import (
    causal_conv as _causal_conv,
    mm as _mm,
    rms_norm as _rms_norm,
)
from distributed_learning_simulator_tpu.ops import selective_scan as _scan

MAMBA, ATTENTION = "mamba", "attention"
#: What keeping a block adds to the round's peak, as a share of what
#: autodiff keeps of it (:func:`lm_parts.rematerialised_blocks`; the other
#: language models' share is ``lm_parts.KEPT_SHARE``). Read from the
#: compiler's count of ``jamba2_fed_seq16k_c2``'s round for a v5e: a Mamba
#: block keeps 3.05 GB at 16,384 positions; the last block kept added 1.69
#: GB to the all-rematerialised peak of 12.96 GB, the last two 4.73 GB
#: (PERF.md § 6). At the shared 0.45 the rule kept two, 17.69 GB by that
#: count: more than the chip has.
KEPT_SHARE = 1.0
#: The range of Mamba's initial steps ``Delta`` (``dt_min``, ``dt_max``).
DT_RANGE = (1e-3, 1e-1)


def a_log_start(channels: int, states: int):
    """``A_log``'s initial value: ``log n`` for ``n = 1..states``, every
    channel."""
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, states + 1, dtype=jnp.float32)),
        (channels, states))


def dt_bias_start(channels: int):
    """``b_dt``'s initial value: ``softplus^-1`` of steps spread evenly in
    ``log Delta`` over :data:`DT_RANGE`, one a channel."""
    low, high = np.log(DT_RANGE)
    dt = np.exp(low + (np.arange(channels) + 0.5) / channels * (high - low))
    return jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32)


def ssm_scan(x, delta, A, B, C, D):
    """The selective scan alone (``ops/selective_scan.py``: a kernel on a
    TPU at whole chunks and lane tiles, the XLA form elsewhere)."""
    with jax.named_scope("ssm_scan"):
        return _scan.selective_scan(x, delta, A, B, C, D)


def mamba_in(p, u, c):
    """A Mamba mixer up to the deployment's exchange: ``x`` (convolved,
    SiLU), the gate ``z``, and this rank's partial ``x W_xproj``."""
    with jax.named_scope("mamba"):
        dtype = jnp.dtype(c.dtype)
        x = jax.nn.silu(_causal_conv(_mm(u, p["in_x"], dtype), p["conv"])
                        + p["conv_bias"].astype(jnp.float32))
        return x, _mm(u, p["in_z"], dtype), _mm(x, p["x_proj"], dtype)


def mamba_out(p, x, z, xdbc, c):
    """A Mamba mixer from ``[delta, B, C]`` (the summed ``x W_xproj``) to
    its output projection."""
    with jax.named_scope("mamba"):
        dtype = jnp.dtype(c.dtype)
        R, N, eps = c.mamba_dt_rank, c.mamba_d_state, c.rms_norm_eps
        E = x.shape[-1]
        delta = _rms_norm(xdbc[..., :R], p["dt_norm"], eps)
        B = _rms_norm(xdbc[..., R:R + N], p["b_norm"], eps)
        C = _rms_norm(xdbc[..., R + N:], p["c_norm"], eps)
        delta = jax.nn.softplus(
            _mm(delta, p["dt_proj"], dtype) + dt_bias_start(E)
            + p["dt_bias_shift"].astype(jnp.float32))
        A = -jnp.exp(a_log_start(E, N) + p["A_log_shift"].astype(
            jnp.float32))
        y = ssm_scan(x, delta, A, B, C, p["D"])
        return _mm(y * jax.nn.silu(z), p["out"], dtype)


def mamba(p, u, c):
    """One rank's Mamba mixer, the exchange left out (one chip)."""
    return mamba_out(p, *mamba_in(p, u, c), c)


def attn_full(p, x, c):
    """An attention layer's held heads, projections to output: no
    positions, no gate, no norm (the fused kernel or the XLA form:
    :func:`lm_parts.attention_core`)."""
    with jax.named_scope("attn_full"):
        return parts.gated_attention(
            p, x, heads=c.heads_held, kv_heads=c.kv_heads_held,
            head_dim=c.head_dim, dtype=jnp.dtype(c.dtype))


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
    "mamba": (mamba_in, mamba_out),
    "ssm_scan": (ssm_scan,),
    "attn_full": (attn_full,),
    "mlp": (mlp,),
    "lm_head": (lm_head, head_nll),
}
#: The named scope whose code holds a source line of this file.
scope_of_line = parts.scope_lookup(_SCOPES)


# --- the module -------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Share:
    """The published sizes and what of each layer is held here."""

    hidden_size: int = 2560
    num_hidden_layers: int = 14
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    heads_held: int = 5
    mamba_expand: int = 2
    channels_held: int = 1280
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 160
    intermediate_size: int = 8192
    mlp_columns_held: int = 2048
    rms_norm_eps: float = 1e-6
    dtype: str = "bfloat16"

    def __post_init__(self):
        held = {
            "heads_held": (self.heads_held, self.num_attention_heads),
            "channels_held": (self.channels_held,
                              self.mamba_expand * self.hidden_size),
            "mlp_columns_held": (self.mlp_columns_held,
                                 self.intermediate_size),
        }
        for name, (part, whole) in held.items():
            if not 0 < part <= whole:
                raise ValueError(f"{name}={part}: between 1 and {whole}")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError(
                f"{self.num_attention_heads} heads over hidden "
                f"{self.hidden_size}: no whole head size")
        group = self.num_attention_heads // self.num_key_value_heads
        if self.num_key_value_heads > 1 and self.heads_held % group:
            raise ValueError(
                f"heads_held={self.heads_held} must be whole groups of "
                f"{group} query heads a key/value head")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_heads_held(self) -> int:
        """One key/value head is whole on every rank; more are divided
        with their groups of query heads."""
        if self.num_key_value_heads == 1:
            return 1
        return self.heads_held // (
            self.num_attention_heads // self.num_key_value_heads)

    @property
    def layer_kinds(self) -> tuple:
        return tuple(
            ATTENTION if i % self.attn_layer_period == self.attn_layer_offset
            else MAMBA for i in range(self.num_hidden_layers))


class _Block(nn.Module):
    cfg: Share
    kind: str

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        D, dtype = c.hidden_size, jnp.dtype(c.dtype)
        x = parts.Norm(c.rms_norm_eps, name="input_norm")(h)
        if self.kind == MAMBA:
            E, N, R = c.channels_held, c.mamba_d_state, c.mamba_dt_rank
            taps = c.mamba_d_conv
            p = parts.Params((
                ("in_x", (D, E), D), ("in_z", (D, E), D),
                ("conv", (taps, E), taps), ("conv_bias", (E,), "zeros"),
                ("x_proj", (E, R + 2 * N), E),
                ("dt_norm", (R,), "ones"), ("b_norm", (N,), "ones"),
                ("c_norm", (N,), "ones"),
                ("dt_proj", (R, E), R), ("dt_bias_shift", (E,), "zeros"),
                ("A_log_shift", (E, N), "zeros"), ("D", (E,), "ones"),
                ("out", (E, D), E),
            ), name="mamba")()
            y = mamba(p, x, c)
        else:
            HD, KV = c.heads_held * c.head_dim, c.kv_heads_held * c.head_dim
            p = parts.Params((
                ("q", (D, HD), D), ("k", (D, KV), D), ("v", (D, KV), D),
                ("o", (HD, D), HD),
            ), name="attn")()
            y = attn_full(p, x, c)
        h = h + y
        x = parts.Norm(c.rms_norm_eps, name="ff_norm")(h)
        F = c.mlp_columns_held
        p = parts.Params((
            ("gate", (D, F), D), ("up", (D, F), D), ("down", (F, D), F),
        ), name="mlp")()
        return h + mlp(p, x, dtype=dtype)


class Jamba(nn.Module):
    """``num_classes`` is the vocabulary this chip holds (what the
    dataset draws its ids from; ``vocab_rows``, if given, must agree);
    ``share`` says what else is held: build with :func:`jamba`."""

    num_classes: int
    share: Share = Share()
    vocab_rows: int | None = None
    #: models/registry.init_params: draw the weights in one program,
    #: traced over this many positions.
    jit_init = True
    init_positions = _scan.CHUNK
    #: The head makes its loss and both its gradients itself and hands
    #: on no logits (lm_parts.head_nll); the recorder's counter.
    head_backward_tied = True

    @property
    def ssm_layers(self) -> int:
        """The recorder's counter of the same name: layers that mix by
        the selective scan."""
        return self.share.layer_kinds.count(MAMBA)

    def selective_scan_kernel(self, positions: int) -> int:
        """1 where the scan's kernels take sequences of ``positions``
        (:func:`ops.selective_scan.kernel_applies`); the recorder's
        counter of the same name is this on a TPU and 0 anywhere else."""
        return int(self.ssm_layers > 0 and _scan.kernel_applies(
            positions, self.share.channels_held))

    def fused_attention_layers(self, positions: int) -> int:
        """Attention layers whose core is the fused kernel where the
        program is lowered for a TPU, at sequences of ``positions``
        (:func:`lm_parts.fused_attention_applies`); the recorder's counter
        of the same name is this on a TPU and 0 anywhere else."""
        c = self.share
        return c.layer_kinds.count(ATTENTION) * parts.fused_attention_applies(
            positions, c.head_dim, c.dtype)

    def _layers(self) -> tuple:
        return tuple((f"layer_{layer}", (self.share, kind))
                     for layer, kind in enumerate(self.share.layer_kinds))

    def remat_blocks(self, params, tokens_shape) -> int:
        """Leading blocks wrapped in ``nn.remat`` at a step of
        ``tokens_shape`` (:func:`lm_parts.rematerialised_blocks`); the
        recorder's counter of the same name."""
        return parts.rematerialised_blocks(
            _Block, self._layers(), params, tokens_shape, self.share,
            kept_share=KEPT_SHARE)

    @nn.compact
    def __call__(self, tokens):
        if self.vocab_rows not in (None, self.num_classes):
            raise ValueError(
                f"vocab_rows={self.vocab_rows} but the dataset draws its "
                f"ids from {self.num_classes}"
            )
        c = self.share
        D = c.hidden_size
        table = parts.Params(
            (("table", (self.num_classes, D), D),), name="embed")()["table"]
        h = jnp.take(table, tokens, axis=0).astype(jnp.float32)
        for block in parts.blocks(self, _Block, tokens.shape):
            h = block(h)
        x = parts.Norm(c.rms_norm_eps, name="final_norm")(h)
        return parts.UnmadeLogits(table.T, x, jnp.dtype(c.dtype),
                                  (lm_head, head_nll))


def jamba(num_classes: int, vocab_rows: int | None = None, **share):
    """The registry's constructor: ``--model_args`` gives ``vocab_rows``,
    the share held (``channels_held``, ``heads_held``,
    ``mlp_columns_held``) and any published size a smaller preset changes,
    flat."""
    return Jamba(num_classes=num_classes, share=Share(**share),
                 vocab_rows=vocab_rows)

