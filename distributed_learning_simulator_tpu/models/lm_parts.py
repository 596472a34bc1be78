"""The parts the language models share (models/solar_open2.py,
models/afmoe.py, models/olmo_hybrid.py): softmax attention (every key at
or before the query, or a band of them) in two forms, the gated delta
rule in chunks (a decay per key channel or one per head), sigmoid routing,
the experts told their share, a SwiGLU, the head that makes its own
loss, and which leading blocks are rematerialised
(:func:`rematerialised_blocks`: the rest keep their residuals).

The attention's core (scores, mask, softmax, weighted values, forward and
backward) has an XLA form, a block of queries at a time with the block's
scores written out (:func:`causal_attention`, :func:`banded_attention`),
and a fused form that keeps them on chip (:func:`fused_attention`: the
installed splash-attention kernel). :func:`attention_core` chooses by what
it can observe: the platform the program is LOWERED for and the shapes.

A device trace names each op by the innermost frame of user code it was
traced from, and a model's per-scope device times are read by the lines
of the MODEL's file (``benchmark/harness/scopes.py``). This file is
therefore registered with JAX as not being user code, like
``traced_helpers.py``: an op made here carries the line of its CALLER, the
scope function of the model's file that wraps each call (``moe_experts``
in ``solar_open2.py``, ``swa`` in ``afmoe.py``, ``gdn`` in
``olmo_hybrid.py``, ...). No
``jax.named_scope`` is opened here except inside the head's gradient
rules, which run outside their caller's.
"""

from __future__ import annotations

import functools
import inspect
import math
from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.extend import core as jex_core
from jax.extend import source_info_util

from distributed_learning_simulator_tpu.models.traced_helpers import (
    HIGHEST,
    ein as _ein,
    mm as _mm,
    rms_norm as _rms_norm,
)
from distributed_learning_simulator_tpu.telemetry.memory import (
    device_memory_limit,
)

source_info_util.register_exclusion(__file__)

#: Queries per block of the softmax attention's XLA form (its scores are
#: materialised a block at a time).
QUERY_BLOCK = 512
#: Positions per chunk of the delta rule, and per sub-block of a chunk in
#: the form with a decay per key channel.
DELTA_CHUNK = 64
DELTA_SUB = 16


class FusedBlocks(NamedTuple):
    """Blocks of the fused attention kernel along the sequence: queries
    and keys a grid step, keys a product inside it. The backward takes
    the same, in ONE kernel (dk/dv with dq's partial sums; splash
    attention's ``use_fused_bwd_kernel``)."""

    q: int
    kv: int
    kv_compute: int


#: Constants read on the chip from the layers timed alone at 8,192 and
#: 4,096 positions, both masks (PERF.md § 6, PR 34: the full layer 8.4 /
#: 24.9 ms forward / forward + backward for 9.4 / 28.6 at 512s and 46.5 /
#: 154 at the kernel's default 128s with its two backward kernels; 2,048
#: queries do not fit VMEM), not knobs.
FUSED_BLOCKS = FusedBlocks(q=1024, kv=1024, kv_compute=512)
#: Slots a held expert has, as a multiple of its even share of a batch's
#: assignments (rounded up to 128 rows, 8 below 128); beyond it the layer
#: falls back to every token (experts).
EXPERT_CAPACITY_FACTOR = 2.0
#: Slots an expert must have before the held experts share their rows and
#: run as one ragged product (two 512-row tiles of the TPU's ragged-dot
#: kernel); below it each expert keeps slots of its own (experts).
RAGGED_MIN_SLOTS = 1024


# --- attention ---------------------------------------------------------------


def _query_blocks(q, block: int):
    """``q`` ``[B, T, kv, group, d]`` as ``[n, B, block, kv, group, d]``,
    the last block padded with zero queries (their rows are dropped by
    :func:`_from_blocks`)."""
    B, T = q.shape[:2]
    n = -(-T // block)
    if n * block > T:
        q = jnp.pad(q, ((0, 0), (0, n * block - T)) + ((0, 0),) * 3)
    return jnp.moveaxis(q.reshape((B, n, block) + q.shape[2:]), 1, 0)


def _from_blocks(o, T: int):
    """``[n, B, block, ...]`` -> ``[B, T, ...]``."""
    o = jnp.moveaxis(o, 0, 1)
    o = o.reshape((o.shape[0], -1) + o.shape[3:])
    return o if o.shape[1] == T else o[:, :T]


def causal_attention(q, k, v, *, dtype, query_block: int = QUERY_BLOCK):
    """Softmax attention of each query over every key at or before it.
    ``q`` ``[B, T, kv, group, d]``, ``k``, ``v`` ``[B, T, kv, d]``;
    returns ``[B, T, kv, group, d]`` f32. One block of queries against
    every key at a time: the scores of a block are all that is ever live,
    forward or backward. ``T`` need not be a multiple of the block: the
    last block is padded with queries whose rows are dropped."""
    T, head_dim = q.shape[1], q.shape[-1]
    block = min(query_block, T)

    @jax.checkpoint
    def attend(args):
        q_blk, first = args  # [B, block, kv, group, d]
        s = _ein("btkgd,bskd->bkgts", q_blk, k, dtype) / math.sqrt(
            head_dim)
        visible = (
            first + jnp.arange(block)[:, None] >= jnp.arange(T)[None, :]
        )
        w = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
        return _ein("bkgts,bskd->btkgd", w, v, dtype)

    blocks = _query_blocks(q, block)
    o = jax.lax.map(
        attend, (blocks, jnp.arange(blocks.shape[0]) * block)
    )  # [n, B, block, kv, group, d]
    return _from_blocks(o, T)


def band_keys(T: int, window: int, query_block: int = QUERY_BLOCK) -> int:
    """Keys a block of queries reads in :func:`banded_attention`: the
    block's own positions and as many before its first as the window
    reaches (no more than there are). ``T`` says every key is read: the
    sequence is one block."""
    block = min(query_block, T)
    return block + min(window, (-(-T // block) - 1) * block)


def banded_attention(q, k, v, *, window: int, dtype,
                     query_block: int = QUERY_BLOCK):
    """Softmax attention of query ``i`` over the keys ``j`` with ``0 <=
    i - j < window``. Shapes as :func:`causal_attention`. A block of
    queries starting at ``first`` reads, multiplies and differentiates
    only the keys of ``[first - back, first + block)``, ``back`` the
    window's reach (:func:`band_keys`): a slice of ``k`` and ``v``, taken
    before the loop over blocks (static slices, stacked: ``k`` and ``v``
    of a few key/value heads are small beside the scores), never a mask
    over all of them. Inside the slice the mask is the definition's."""
    B, T = q.shape[:2]
    head_dim = q.shape[-1]
    block = min(query_block, T)
    n = -(-T // block)
    keys = band_keys(T, window, query_block)
    back = keys - block

    def bands(a):  # [B, T, kv, d] -> [n, B, keys, kv, d]
        a = jnp.pad(a.astype(dtype),
                    ((0, 0), (back, n * block - T), (0, 0), (0, 0)))
        return jnp.stack(
            [a[:, i * block:i * block + keys] for i in range(n)])

    @jax.checkpoint
    def attend(args):
        q_blk, k_band, v_band, first = args
        s = _ein("btkgd,bskd->bkgts", q_blk, k_band, dtype) / math.sqrt(
            head_dim)
        at = first + jnp.arange(block)[:, None]
        key_at = first - back + jnp.arange(keys)[None, :]
        visible = (key_at >= 0) & (key_at <= at) & (at - key_at < window)
        w = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
        return _ein("bkgts,bskd->btkgd", w, v_band, dtype)

    o = jax.lax.map(
        attend,
        (_query_blocks(q, block), bands(k), bands(v),
         jnp.arange(n) * block),
    )
    return _from_blocks(o, T)


def rotary(x, theta: float):
    """Rotary positions over all of the last axis, rotate-half form:
    ``x`` ``[B, T, ..., d]`` f32, position ``t`` of axis 1."""
    T, d = x.shape[1], x.shape[-1]
    inverse = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inverse  # [T, d/2]
    angle = jnp.concatenate([angle, angle], axis=-1).reshape(
        (1, T) + (1,) * (x.ndim - 3) + (d,))
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * jnp.cos(angle) + jnp.concatenate(
        [-x2, x1], axis=-1) * jnp.sin(angle)


def fused_attention_applies(T: int, head_dim: int, dtype,
                            blocks=FUSED_BLOCKS) -> bool:
    """Whether the fused kernel takes sequences of ``T`` positions over
    heads of ``head_dim`` with products in ``dtype``, by shapes alone:
    the head a multiple of the 128 lanes, ``T`` whole blocks of the
    kernel, and the products in bfloat16 (the kernel multiplies f32
    operands in one bf16 pass, which is not what an f32 configuration
    states). Any other shape keeps the XLA form and its padded last
    block. Whether it RUNS is also the lowering platform's to say
    (:func:`attention_core`)."""
    return (head_dim % 128 == 0 and T % max(blocks.q, blocks.kv) == 0
            and jnp.dtype(dtype) == jnp.bfloat16)


@functools.lru_cache(maxsize=None)
def _fused_kernel(T: int, window: int | None, group: int, blocks,
                  interpret: bool):
    """The splash-attention kernel of ``group`` query heads over ONE
    key/value head (its MQA form) at ``T`` positions, built once per
    shape and not once a layer a trace: the mask's block tables are
    made on the host. ``LocalMask((window - 1, 0))`` is ``0 <= i - j <
    window``."""
    # Imported here: a second of import that only a program with such a
    # layer pays.
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as _splash,
        splash_attention_mask as _splash_mask,
    )

    shape = (T, T)
    mask = _splash_mask.CausalMask(shape) if window is None else (
        _splash_mask.LocalMask(shape, (window - 1, 0), 0))
    with jax.ensure_compile_time_eval():
        return _splash.make_splash_mqa_single_device(
            _splash_mask.MultiHeadMask([mask] * group),
            block_sizes=_splash.BlockSizes(
                block_q=blocks.q, block_kv=blocks.kv,
                block_kv_compute=blocks.kv_compute,
                block_q_dkv=blocks.q, block_kv_dkv=blocks.kv,
                block_kv_dkv_compute=blocks.kv_compute,
                use_fused_bwd_kernel=True),
            interpret=interpret)


def fused_attention(q, k, v, *, window: int | None, dtype,
                    blocks=FUSED_BLOCKS, interpret: bool = False):
    """:func:`causal_attention` (``window`` None) or
    :func:`banded_attention` as ONE fused kernel, forward and backward:
    a block of queries against a block of keys at a time with an online
    softmax, the scores never leaving VMEM, and key blocks the mask hides
    whole never read. Shapes as :func:`causal_attention`; ``T`` and the
    head must satisfy :func:`fused_attention_applies`. Products in
    ``dtype`` accumulated f32, the softmax f32; ``1 / sqrt(d)`` goes into
    ``q`` in f32 before the cast. The kernel hands its output on in
    ``dtype`` (returned as f32 like the XLA form's, whose f32 the gate
    multiply and the output projection round one step later); its
    residuals are ``q``, ``k``, ``v``, the output and the rows' log-sum-
    exp. ``interpret`` runs the kernel in Pallas's interpreter (the CPU
    tests)."""
    T, group, head_dim = q.shape[1], q.shape[3], q.shape[4]
    kernel = _fused_kernel(T, window, group, blocks, interpret)
    q = (q.astype(jnp.float32) / math.sqrt(head_dim)).astype(dtype)
    # JAX registers its Pallas ops as user code, and an inclusion beats
    # this file's exclusion: left alone the kernel's ops would carry a
    # line of splash_attention_kernel.py and no scope's time would hold
    # them. They are given the line everything else made here carries:
    # the caller's. (JAX keeps the kernel's traced body by its shapes,
    # lines and all: a second model of the same attention shapes in one
    # process would show the first one's lines. A run holds one model.)
    here = source_info_util.current()
    with source_info_util.user_context(here.traceback,
                                       name_stack=here.name_stack):
        o = jax.vmap(jax.vmap(kernel))(  # over B and kv
            jnp.transpose(q, (0, 2, 3, 1, 4)),  # [group, T, d] a call
            jnp.transpose(k.astype(dtype), (0, 2, 1, 3)),  # [T, d]
            jnp.transpose(v.astype(dtype), (0, 2, 1, 3)),
        )  # [B, kv, group, T, d]
    return jnp.transpose(o, (0, 3, 1, 2, 4)).astype(jnp.float32)


def attention_core(q, k, v, *, window: int | None, dtype,
                   query_block: int = QUERY_BLOCK):
    """Softmax attention of each query over the keys it sees (every key
    at or before it, or with ``window`` the band ``0 <= i - j <
    window``), in the form the code can observe to be the right one:

    * shapes the kernel does not take (:func:`fused_attention_applies`):
      the XLA form, as ever;
    * else ``lax.platform_dependent``: :func:`fused_attention` where the
      program is LOWERED for a TPU (a compile for a described chip under
      ``JAX_PLATFORMS=cpu`` included), the XLA form on any other
      platform (the CPU tests' path, and the oracle).

    No flag, no configuration field, no look at ``jax.default_backend()``:
    tracing does not know the platform, lowering does."""
    def xla(q, k, v):
        if window is None:
            return causal_attention(q, k, v, dtype=dtype,
                                    query_block=query_block)
        return banded_attention(q, k, v, window=window, dtype=dtype,
                                query_block=query_block)

    if not fused_attention_applies(q.shape[1], q.shape[-1], dtype):
        return xla(q, k, v)
    return jax.lax.platform_dependent(
        q, k, v, default=xla,
        tpu=functools.partial(fused_attention, window=window, dtype=dtype))


def gated_attention(p, x, *, heads: int, kv_heads: int, head_dim: int,
                    dtype, query_block: int = QUERY_BLOCK,
                    qk_norm_eps: float | None = None,
                    qk_norm: str = "head",
                    window: int | None = None,
                    rope_theta: float | None = None):
    """Grouped-query softmax attention over ``heads`` query heads and
    their ``kv_heads`` key/value heads; ``x`` the input ``[B, T, D]``,
    ``p`` the projections ``q``, ``k``, ``v``, ``o`` and, where it holds
    ``g``, a sigmoid gate per output channel before the output
    projection. With ``qk_norm_eps`` an RMS norm on ``q`` and ``k``
    (scales ``q_norm``, ``k_norm``): per head (``qk_norm="head"``) or over
    the whole projection (``"projection"``, before it is split into
    heads); with ``rope_theta`` rotary positions on both; with ``window``
    the band ``0 <= i - j < window``, else every key at or before the
    query. Projections, norm, positions, gate and output projection are
    XLA's everywhere; the core between them is :func:`attention_core`'s
    choice (``query_block`` sizes its XLA form only)."""
    B, T, _ = x.shape
    whole = qk_norm_eps is not None and qk_norm == "projection"

    def project(name):
        y = _mm(x, p[name], dtype)
        return _rms_norm(y, p[name + "_norm"], qk_norm_eps) if whole else y

    q = project("q").reshape(B, T, kv_heads, heads // kv_heads, head_dim)
    k = project("k").reshape(B, T, kv_heads, head_dim)
    v = _mm(x, p["v"], dtype).reshape(B, T, kv_heads, head_dim)
    if qk_norm_eps is not None and not whole:
        q = _rms_norm(q, p["q_norm"], qk_norm_eps)
        k = _rms_norm(k, p["k_norm"], qk_norm_eps)
    if rope_theta is not None:
        q, k = rotary(q, rope_theta), rotary(k, rope_theta)
    o = attention_core(q, k, v, window=window, dtype=dtype,
                       query_block=query_block)
    if "g" not in p:
        return _mm(o.reshape(B, T, heads * head_dim), p["o"], dtype)
    gate = jax.nn.sigmoid(_mm(x, p["g"], dtype))
    return _mm(o.reshape(B, T, heads * head_dim) * gate, p["o"], dtype)


# --- the gated delta rule, chunked -------------------------------------------


def _intra_chunk(qc, kc, gc, sub: int):
    """Decay-weighted products inside one chunk, all f32.

    ``A[t, s] = sum_d k_t k_s exp(g_t - g_s)`` for ``s < t`` and ``P[t, s]
    = sum_d q_t k_s exp(g_t - g_s)`` for ``s <= t``, ``g`` the log decay
    summed from the chunk's start (falling with ``t``). Shapes ``[..., C,
    d]`` -> ``[..., C, C]``; ``gc`` ``[..., C, d]``, or ``[..., C, 1]`` for
    one decay a head (:func:`_intra_chunk_scalar`).

    A decay per key channel: ``exp(-g_s)`` alone overflows under a strong
    decay, so no factor above 1 is ever formed: between sub-blocks of
    ``sub`` positions both operands are scaled against the decay at the
    later block's start; inside a sub-block the ``[sub, sub, d]``
    differences are exponentiated directly.
    """
    if gc.shape[-1] == 1:
        return _intra_chunk_scalar(qc, kc, gc)
    C, d = kc.shape[-2:]
    n = C // sub
    lead = kc.shape[:-2]

    def blocks(a):
        return a.reshape(lead + (n, sub, d))

    qb, kb, gb = blocks(qc), blocks(kc), blocks(gc)
    # Decay at the end of the sub-block before: 0 for the first.
    start = jnp.concatenate(
        [jnp.zeros_like(gb[..., :1, -1, :]), gb[..., :-1, -1, :]], axis=-2
    )  # [..., n, d]
    left = jnp.exp(gb - start[..., None, :])  # rows of block i: <= 1
    # Columns s scaled for each later block i: exp(start_i - g_s) <= 1
    # where s lies before block i; elsewhere unused (masked to 0 below).
    right = jnp.exp(jnp.minimum(
        start[..., :, None, :] - gc[..., None, :, :], 0.0
    ))  # [..., n, C, d]
    before = (
        jnp.arange(C)[None, :] // sub < jnp.arange(n)[:, None]
    )  # [n, C]
    k_right = kc[..., None, :, :] * right * before[..., None]

    def off_diagonal(rows):
        out = jnp.einsum("...ntd,...nsd->...nts", rows * left, k_right,
                         precision=HIGHEST)
        return out.reshape(lead + (C, C))

    # Inside a sub-block: every pair's own difference, never positive
    # where it is kept (s <= t).
    diff = gb[..., :, None, :] - gb[..., None, :, :]  # [..., n, t, s, d]
    keep = jnp.tril(jnp.ones((sub, sub), bool))
    decay = jnp.exp(jnp.where(keep[..., None], diff, -jnp.inf))

    def diagonal(rows):
        out = jnp.einsum("...ntd,...nsd,...ntsd->...nts", rows, kb, decay,
                         precision=HIGHEST)  # [..., n, sub, sub]
        eye = jnp.eye(n, dtype=out.dtype)
        full = jnp.einsum("...nts,nm->...ntms", out, eye)
        return full.reshape(lead + (C, C))

    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    A = jnp.where(strict, off_diagonal(kb) + diagonal(kb), 0.0)
    P = off_diagonal(qb) + diagonal(qb)  # zero above the diagonal already
    return A, P


def _intra_chunk_scalar(qc, kc, gc):
    """:func:`_intra_chunk` for ONE decay a head and position (``gc``
    ``[..., C, 1]``): the decay factors out of the sum over channels, so
    ``A = (K K^T) * G`` and ``P = (Q K^T) * G`` with ``G[t, s] = exp(g_t -
    g_s)`` for ``s <= t``: two ``[C, C]`` products and one ``[C, C]``
    matrix of differences, never positive where it is kept."""
    C = kc.shape[-2]
    g = gc[..., 0]
    keep = jnp.tril(jnp.ones((C, C), bool))
    decay = jnp.exp(jnp.where(keep, g[..., :, None] - g[..., None, :],
                              -jnp.inf))

    def product(rows):
        return jnp.einsum("...td,...sd->...ts", rows, kc, precision=HIGHEST)

    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    return jnp.where(strict, product(kc) * decay, 0.0), product(qc) * decay


def _unit_lower_inverse(L):
    """``(I - L)^-1`` for strictly lower-triangular ``L`` ``[..., C, C]``:
    ``L`` is nilpotent, so the inverse is ``prod_j (I + L^(2^j))``."""
    C = L.shape[-1]
    eye = jnp.eye(C, dtype=L.dtype)
    inv = eye + L
    power = L
    for _ in range(max(0, math.ceil(math.log2(C)) - 1)):
        power = jnp.matmul(power, power, precision=HIGHEST)
        inv = jnp.matmul(inv, eye + power, precision=HIGHEST)
    return inv


def chunked_delta_rule(q, k, v, log_decay, beta, dtype=jnp.bfloat16,
                       chunk: int = DELTA_CHUNK, sub: int = DELTA_SUB):
    """``S_t = (I - b_t k_t k_t^T) Diag(a_t) S_(t-1) + b_t k_t v_t^T``,
    ``o_t = S_t^T q_t``, ``S_0 = 0``, in chunks of ``chunk`` positions.

    Inside a chunk the rank-one updates are folded into one triangular
    system (the WY / UT-transform form): with ``u_t = b_t (v_t - S_(t-1)^T
    Diag(a_t) k_t)`` the state is ``S_t = Diag(a_t) S_(t-1) + k_t u_t^T``,
    and over a chunk ``(I + Diag(b) A) U = Diag(b) (V - K+ S_0)`` with
    ``A`` of :func:`_intra_chunk` and ``K+ = K exp(g)``. The triangular
    inverse and ``A``, ``P`` are f32; the products with the ``[d_k,
    d_v]`` state multiply in ``dtype`` and accumulate f32; the state is
    carried between chunks by ``lax.scan`` in f32.

    ``q, k`` ``[B, T, H, d_k]``, ``v`` ``[B, T, H, d_v]`` f32, ``beta``
    ``[B, T, H]``; ``log_decay`` ``[B, T, H, d_k]`` (a decay per key
    channel: Kimi's KDA) or ``[B, T, H]`` / ``[B, T, H, 1]`` (one a head:
    Gated DeltaNet), the form chosen by that shape. Returns ``[B, T, H,
    d_v]`` f32. ``T`` need not be a multiple of ``chunk``: padded
    positions have ``k = v = 0`` and no decay, so they leave the state as
    it is.
    """
    if log_decay.ndim == 3:
        log_decay = log_decay[..., None]
    B, T, H, d = q.shape
    pad = (-T) % chunk
    if pad:
        q, k, v, log_decay = (
            jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
            for a in (q, k, v, log_decay)
        )
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n = (T + pad) // chunk

    def chunks(a):  # [B, T, H, d] -> [n, B, H, C, d]
        a = a.reshape((B, n, chunk) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    qc, kc, vc, gc = (chunks(a) for a in (q, k, v, log_decay))
    bc = jnp.moveaxis(
        jnp.moveaxis(beta.reshape(B, n, chunk, H), 3, 2), 1, 0
    )  # [n, B, H, C]
    gc = jnp.cumsum(gc, axis=-2)
    # Recomputed in the backward pass: its [sub, sub, d] decay factors are
    # the mixer's largest tensors.
    A, P = jax.checkpoint(_intra_chunk, static_argnums=(3,))(qc, kc, gc, sub)
    solve = _unit_lower_inverse(-bc[..., None] * A) * bc[..., None, :]
    decay = jnp.exp(gc)
    k_in = kc * decay  # against the state at the chunk's start
    q_in = qc * decay
    total = gc[..., -1:, :]
    k_out = kc * jnp.exp(total - gc)  # towards the state at its end
    W = _ein("...ts,...sd->...td", solve, k_in, dtype)
    U0 = _ein("...ts,...sd->...td", solve, vc, dtype)

    def step(S, xs):
        W_c, U0_c, q_c, P_c, k_c, total_c = xs
        U = U0_c - _ein("...td,...de->...te", W_c, S, dtype)
        out = _ein("...td,...de->...te", q_c, S, dtype) + _ein(
            "...ts,...se->...te", P_c, U, dtype
        )
        S = S * jnp.exp(total_c)[..., 0, :, None] + _ein(
            "...td,...te->...de", k_c, U, dtype
        )
        return S, out

    S0 = jnp.zeros((B, H, d, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(step, S0, (W, U0, q_in, P, k_out, total))
    out = jnp.moveaxis(jnp.moveaxis(out, 0, 1), 2, 3)  # [B, n, C, H, d]
    return out.reshape(B, n * chunk, H, -1)[:, :T]


# --- the expert layer --------------------------------------------------------


def expert_capacity(n_tokens: int, top_k: int, n_experts: int) -> int:
    even = n_tokens * top_k / n_experts
    rows = math.ceil(even * EXPERT_CAPACITY_FACTOR)
    tile = 128 if rows >= 128 else 8
    return min(n_tokens, math.ceil(rows / tile) * tile)


def route(router, x, *, top_k: int, expert_offset: int, experts_held: int,
          bias=None, eps: float = 0.0, scale: float = 1.0):
    """Score every expert of the layer (f32: a near-tie between the
    ``top_k``-th and the next score decides where a token goes), choose
    the ``top_k`` largest of score (+ ``bias``, which selects and does
    not weigh), normalise the chosen scores (their sum + ``eps``) and
    scale them; ``x`` ``[N, D]``. Returns the combine weight of each held
    expert for each token ``[N, held]`` (0 where the token did not choose
    it)."""
    scores = jax.nn.sigmoid(_mm(x, router, jnp.float32))
    if bias is None:
        top, index = jax.lax.top_k(scores, top_k)
    else:
        _, index = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        top = jnp.take_along_axis(scores, index, axis=-1)
    total = jnp.sum(top, -1, keepdims=True)
    weight = top / (total + eps if eps else total)
    if scale != 1.0:
        weight = weight * scale
    local = index - expert_offset  # [N, top_k]
    held = jnp.arange(experts_held)
    return jnp.sum(
        weight[..., None] * (local[..., None] == held), axis=-2
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gather_rows(n: int, x, token):
    """``x[token]``: the row of each token ``token`` names, where ``N`` (a
    padding row) reads token ``N - 1``; the gradient of a padding row is
    dropped (:func:`_scatter_rows` is this gather's transpose, and it is
    the scatter's): neither direction selects over the rows."""
    return x.at[token].get(mode="clip")


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scatter_rows(n: int, rows, token):
    """``[n, D]``: each token the sum of the ``rows`` that name it, in the
    rows' dtype; rows naming token ``n`` (padding) are dropped."""
    return jnp.zeros((n, rows.shape[1]), rows.dtype).at[token].add(
        rows, mode="drop")


def _rows_forward(fn):
    return lambda n, a, token: (fn(n, a, token), token)


_gather_rows.defvjp(_rows_forward(_gather_rows),
                    lambda n, token, ct: (_scatter_rows(n, ct, token), None))
_scatter_rows.defvjp(_rows_forward(_scatter_rows),
                     lambda n, token, ct: (_gather_rows(n, ct, token), None))


def _ragged(rows, kernels, sizes, dtype):
    """``rows[i] @ kernels[e]`` for the rows of each expert ``e``, the rows
    sorted by expert and ``sizes[e]`` of them each: multiplied in
    ``dtype``, accumulated and returned f32 (``lax.ragged_dot``: on the
    TPU one kernel over tiles of rows, each tile against its expert).

    Rows past the last expert's belong to no product, and the TPU's
    kernel leaves whatever was in memory there (the CPU's writes zeros),
    in the result AND, through the backward pass's own ragged product, in
    the gradient of ``rows``: :func:`experts` never reads them."""
    precision = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
    return jax.lax.ragged_dot(
        rows.astype(dtype), kernels.astype(dtype), sizes,
        precision=precision, preferred_element_type=jnp.float32)


def experts(p, x, combine, *, capacity: int, dtype):
    """The held experts' part of the layer for the tokens routed to them.

    The work follows the tokens routed here, not the tokens in the batch,
    in one of two forms, chosen by the slots an expert has (``capacity``,
    :func:`expert_capacity`: twice its even share of a batch's
    assignments):

    * fewer than ``RAGGED_MIN_SLOTS``: each held expert gathers the tokens
      that chose it into its own ``capacity`` slots and the experts run as
      one grouped product over ``[held, capacity]`` (a hundred rows an
      expert: Solar-Open2's cell);
    * else: the assignments are sorted by expert into ``held * capacity``
      rows that the experts SHARE, and the experts run as one ragged
      product over them (``lax.ragged_dot``), each over exactly the rows
      that chose it. With 512 rows an expert expected and random routers
      the fullest expert of 16 takes 3.6 times its even share (AFMoE's
      cell, PERF.md § 6, PR 33): slots of its own would overflow in every
      step, shared rows overflow only when ALL the held experts together
      are chosen twice as often as expected. The rows are a permutation
      of the assignments: one sort of the (token, expert) pairs, a gather
      of the tokens and one scatter-add back.
      Rows past the assignments name token ``N``: they read token ``N -
      1`` going in and are dropped coming out, both ways
      (:func:`_gather_rows`), so nothing the kernel leaves unwritten there
      is read and no select over the rows is needed.

    No token is dropped: when the slots (of any expert, or in all) do not
    hold the assignments, the layer computes every held expert over every
    token with the combine weights as a mask (``lax.cond``; counted in
    ``overflow``). Returns ``(y [N, D], load [held], overflow)``.
    """
    n_tokens, held = combine.shape
    chosen = combine > 0  # [N, held]
    load = jnp.sum(chosen, axis=0).astype(jnp.int32)
    empty = jnp.zeros((n_tokens, x.shape[-1]), jnp.float32)

    def run(xg):  # [held, rows, D]
        gate = _ein("erd,edf->erf", xg, p["gate"], dtype)
        up = _ein("erd,edf->erf", xg, p["up"], dtype)
        return _ein("erf,efd->erd", jax.nn.silu(gate) * up, p["down"],
                    dtype)

    def grouped(_):
        # The first ``capacity`` choosers of each expert, by position.
        order = jnp.where(
            chosen, (n_tokens - jnp.arange(n_tokens))[:, None], 0
        ).astype(jnp.float32).T  # [held, N]
        rank, index = jax.lax.top_k(order, capacity)
        weight = jnp.where(
            rank > 0, jnp.take_along_axis(combine.T, index, axis=1), 0.0
        )  # [held, capacity]
        y = run(jnp.take(x, index, axis=0)) * weight[..., None]
        return empty.at[index.reshape(-1)].add(y.reshape(-1, y.shape[-1]))

    def ragged(_):
        # Pair (token t, expert e) at place e * N + t, past every
        # assignment where it is none: one sort of unique keys puts the
        # assignments first, by expert and within one by token.
        rows, pairs = held * capacity, chosen.size
        place = jnp.arange(pairs, dtype=jnp.int32)
        key = jnp.where(chosen.T.reshape(-1), place, place + pairs)
        pair = jax.lax.sort(key, is_stable=False)[:rows] % pairs
        real = jnp.arange(rows) < jnp.sum(load)
        token = jnp.where(real, pair % n_tokens, n_tokens)
        weight = jnp.where(real, combine.T.reshape(-1)[pair], 0.0)
        xs = _gather_rows(n_tokens, x, token)
        hidden = jax.nn.silu(_ragged(xs, p["gate"], load, dtype)) * _ragged(
            xs, p["up"], load, dtype)
        y = _ragged(hidden, p["down"], load, dtype) * weight[:, None]
        return _scatter_rows(n_tokens, y, token)

    def every_token(_):
        # One held expert after another over every token, the
        # combine weight as the mask.
        @jax.checkpoint
        def one(y, expert):
            gate, up, down, weight = expert
            hidden = jax.nn.silu(_mm(x, gate, dtype)) * _mm(x, up, dtype)
            return y + _mm(hidden, down, dtype) * weight[:, None], None

        y, _ = jax.lax.scan(
            one, empty, (p["gate"], p["up"], p["down"], combine.T),
        )
        return y

    if capacity >= n_tokens:
        return every_token(None), load, jnp.any(load > capacity)
    if capacity >= RAGGED_MIN_SLOTS:
        overflow = jnp.sum(load) > held * capacity
        return jax.lax.cond(overflow, every_token, ragged, None), load, \
            overflow
    overflow = jnp.any(load > capacity)
    return jax.lax.cond(overflow, every_token, grouped, None), load, \
        overflow


def swiglu(x, gate, up, down, *, dtype):
    hidden = jax.nn.silu(_mm(x, gate, dtype)) * _mm(x, up, dtype)
    return _mm(hidden, down, dtype)


def routing_counts(loads, overflows, n_tokens: int) -> dict:
    """The routing counters of a batch, one entry an expert layer: what
    the engine sums into the round's aux outputs (``model_counts``)."""
    load = jnp.stack(loads)  # [expert layers, held]
    return {
        "moe_local_assignments": jnp.sum(load, axis=1),
        "moe_routed_tokens": jnp.full((len(loads),), n_tokens, jnp.int32),
        "moe_overflows": jnp.stack(overflows).astype(jnp.int32),
        "moe_expert_load": load,
    }


# --- the head ----------------------------------------------------------------


def lm_head(kernel, x, *, dtype):
    """Logits over the rows of the vocabulary held, accumulated f32 and
    kept in ``dtype``: at ``[tokens, 24576]`` they are the largest
    activation of the step, and the loss takes its softmax in f32.
    The model does not hand them to the engine: :func:`head_nll`."""
    return _mm(x, kernel, dtype).astype(dtype)


def _head_loss(kernel, x, targets, weight):
    """The head's logits, made once, and everything of vocabulary width
    that the loss and its gradient need from them; ``kernel`` and ``x``
    are in the products' dtype. The values are ``jax.nn.log_softmax``'s
    and ``take_along_axis``'s, operation for operation."""
    logits = lm_head(kernel, x, dtype=x.dtype)
    f32 = logits.astype(jnp.float32)
    shifted = f32 - jnp.max(f32, axis=-1, keepdims=True)
    e = jnp.exp(shifted)
    total = jnp.sum(e, axis=-1, keepdims=True)
    hit = jnp.arange(f32.shape[-1]) == targets[..., None]
    nll = jnp.log(total)[..., 0] - jnp.sum(
        jnp.where(hit, shifted, 0.0), axis=-1)
    correct = (jnp.argmax(logits, axis=-1) == targets).astype(jnp.float32)
    out = jnp.sum(weight * nll), jnp.sum(weight * correct)
    return out, (e, total, hit, nll, correct)


@jax.custom_vjp
def _head_nll(kernel, x, targets, weight):
    return _head_loss(kernel, x, targets, weight)[0]


def _head_nll_forward(kernel, x, targets, weight):
    with jax.named_scope("lm_head"):
        out, (e, total, hit, nll, correct) = _head_loss(
            kernel, x, targets, weight)
        # d(sum weight * nll) / d logits = weight * (softmax - one_hot)
        # in autodiff's order of operations, rounded to the logits' dtype
        # as autodiff rounds the cotangent of ``.astype(dtype)``, and
        # written out ONCE (the barrier): left free it is fused into
        # both products, or written out in f32, twice the bytes.
        w = weight[..., None]
        share = e * (w / total)
        d_logits = jax.lax.optimization_barrier(
            jnp.where(hit, share - w, share).astype(x.dtype))
        # Autodiff's two products: operands in ``dtype``, accumulated
        # f32, each gradient rounded to its operand's dtype.
        d_kernel = _ein("...d,...v->dv", x, d_logits, x.dtype).astype(
            kernel.dtype)
        d_x = _ein("...v,dv->...d", d_logits, kernel, x.dtype).astype(
            x.dtype)
        # Tied: the input gradient cannot leave for the blocks' backward
        # before the weight gradient exists. Left free, the weight
        # gradient (it feeds only the parameter update) is scheduled
        # after the blocks' backward, and what it reads is dropped and
        # made again for it. Nothing of vocabulary width leaves this rule.
        d_kernel, d_x = jax.lax.optimization_barrier((d_kernel, d_x))
        return out, (d_kernel, d_x, nll, correct)


def _head_nll_backward(residuals, cotangents):
    d_kernel, d_x, nll, correct = residuals
    ct, ct_correct = cotangents  # 1.0 and 0.0 under value_and_grad
    with jax.named_scope("lm_head"):
        return (
            (ct * d_kernel).astype(d_kernel.dtype),
            (ct * d_x).astype(d_x.dtype),
            None,  # integer targets
            ct * nll + ct_correct * correct,
        )


_head_nll.defvjp(_head_nll_forward, _head_nll_backward)


def head_nll(kernel, x, targets, weight, *, dtype):
    """``(sum weight * nll, sum weight * correct)`` of softmax
    cross-entropy over the head's logits, one target and one weight a
    position, WITHOUT handing the logits on: the forward rule of the
    gradient makes the logits once, takes the f32 softmax, and makes
    ``d_logits``, the input gradient ``d_logits @ kernel^T`` and the
    weight gradient ``x^T @ d_logits`` there and then; the backward rule
    scales those two by the scalar cotangent. Roundings are plain
    autodiff's: logits rounded to ``dtype`` before the f32 softmax,
    ``d_logits`` rounded to ``dtype`` before both products.

    Do not simplify this back to ``lm_head`` plus the engine's loss, and
    keep both barriers of the forward rule. Handed the logits, plain
    autodiff made the loss's backward out of vocabulary-wide f32 tensors
    (the cotangent of ``take_along_axis`` scattered into a dense ``[4096,
    24576]`` f32 array over a broadcast of zeros, 403 MB each, then a
    604 MB subtract), and XLA's rematerialisation dropped the 201 MB
    logits after the forward softmax and made the product again for the
    backward: a pair ``fusion.N`` / ``fusion.N.remat`` in each of the two
    unrolled local steps, 4.62 ms an execution at 91 % of the MXU's
    peak, 2 x 36.96 = 73.9 ms of a 2502.5 ms round in
    ``solar_open2_fed_seq4k_c8`` (PERF_LEDGER.jsonl, PR 31). Room did not
    cure it (PR 30 donated the global model and the compiler spent the
    3.4 GB elsewhere). Nor did a barrier on the head's two gradients
    alone (the twin's consumer was the loss's backward), nor this rule
    without its barriers: the weight gradient feeds only the parameter
    update, so XLA fused the softmax into it, scheduled it after the
    blocks' backward and remade the logits for it there. What each form
    compiled to, and what the chip read: PERF.md § 6, PR 32.
    """
    return _head_nll(kernel.astype(dtype), x.astype(dtype), targets, weight)


@jax.tree_util.register_pytree_node_class
class UnmadeLogits:
    """What a model hands on in place of logits: the head's kernel and
    its normed input. The engine's loss asks it for :meth:`weighted_nll`
    (:func:`head_nll`: no logits leave the head); whoever wants the
    logits themselves asks for :meth:`logits`. ``head`` is the pair of
    scope functions of the MODEL's file, ``(lm_head, head_nll)``, each
    around the function of that name here: the head's ops carry the
    model's lines."""

    def __init__(self, kernel, x, dtype, head):
        self.kernel, self.x, self.dtype, self.head = kernel, x, dtype, head

    def logits(self):
        return self.head[0](self.kernel, self.x, dtype=self.dtype)

    def weighted_nll(self, targets, weight):
        return self.head[1](self.kernel, self.x, targets, weight,
                            dtype=self.dtype)

    def tree_flatten(self):
        return (self.kernel, self.x), (self.dtype, self.head)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


# --- which blocks keep their activations for the backward ------------------

#: The round program's peak with every block rematerialised, by what a
#: model can see: bytes a parameter (12 of them the engine's state for the
#: client in flight: the f32 global, the f32 sum of the updates, the
#: client's bf16 copy and its bf16 gradient) and bytes a value of the
#: residual stream a step carries (tokens x hidden: the stream between the
#: blocks, the recomputed block, the head). Fitted to the three
#: language-model cells on a v5e: peaks of 13.14, 15.18 and 16.45 GB
#: read, 13.07, 15.10 and 16.26 counted (PERF.md § 6).
FOOTPRINT_PER_PARAMETER = 15
FOOTPRINT_PER_STREAM_VALUE = 148
#: What keeping a block adds to that peak, as a share of what autodiff
#: keeps of it (:func:`residual_bytes`): XLA fuses elementwise chains into
#: their consumers, and the first block kept takes the place of the one
#: recomputed. Read on a v5e, keeping the last one to four blocks of
#: ``trinity_mini_fed_seq8k_c4``: 0.17, 0.25, 0.31, 0.34. Above all of
#: them, for a need that is not monotone in the blocks kept (PERF.md § 6).
KEPT_SHARE = 0.45
#: The share of the device's ``bytes_limit`` the round may fill (14.8 of
#: a v5e's 15.75 GiB). The rest covers the runtime holding more than the
#: compiler counted, and a program that does not fit, which the compiler
#: fits by recomputing on its own: the blocks are then slower than
#: rematerialised and the peak reaches the limit (PERF.md § 6).
REMAT_FILL = 0.94


def leading_remat(n: int, last_first, room: float) -> int:
    """How many LEADING blocks of a stack of ``n`` are rematerialised,
    given the bytes each block's residuals would hold kept, LAST block
    first (``last_first``, drawn only while they fit), and the ``room``
    for them: the last blocks keep theirs while together they fit. A
    block's residuals live from its forward to its own backward, and the
    backward runs the blocks in reverse, so the first block's live
    longest (over every later block's forward and backward): the first
    blocks are the ones recomputed, and no block is recomputed after one
    that keeps."""
    if room <= 0:
        return n
    total = 0
    for kept, size in enumerate(last_first):
        total += size
        if total > room:
            return n - kept
    return 0


def residual_bytes(fn, *args) -> int:
    """Bytes of what autodiff keeps from ``fn(*args)``'s forward for its
    backward (``jax.linearize``'s residuals, the arguments themselves
    left out: they are alive anyway), counted from shapes: ``args`` may
    be ``jax.ShapeDtypeStruct``."""
    jaxpr = jax.make_jaxpr(lambda *a: jax.linearize(fn, *a)[1])(*args).jaxpr
    kept = {v for v in jaxpr.outvars if isinstance(v, jex_core.Var)}
    return sum(v.aval.size * v.aval.dtype.itemsize
               for v in kept - set(jaxpr.invars))


@functools.lru_cache(maxsize=None)
def _block_bytes(block, args, leaves, tree, stream) -> int:
    module = block(*args, parent=None)
    return residual_bytes(
        lambda p, h: module.apply({"params": p}, h),
        jax.tree_util.tree_unflatten(tree, leaves), stream)


def rematerialised_blocks(block, layers, params, tokens_shape, share,
                          kept_share: float = KEPT_SHARE) -> int:
    """How many leading blocks of a model's stack to wrap in ``nn.remat``
    (:func:`leading_remat`), from the tokens a step, the model's widths
    and the device's ``bytes_limit``. ``layers`` is ``((name, args of
    block), ...)`` in order, ``params`` the model's parameters (arrays or
    shapes; the client's copy is in the products' dtype, ``share.dtype``).
    A kept block costs ``kept_share`` of what autodiff keeps of it
    (:func:`residual_bytes` at the stream ``[*tokens_shape,
    share.hidden_size]`` f32); the room is ``REMAT_FILL`` of the limit
    less the round's peak with every block rematerialised
    (``FOOTPRINT_PER_*``)."""
    stream = jax.ShapeDtypeStruct((*tokens_shape, share.hidden_size),
                                  jnp.float32)
    dtype = jnp.dtype(share.dtype)

    def kept_bytes(name, args):
        leaves, tree = jax.tree_util.tree_flatten(params[name])
        return kept_share * _block_bytes(block, args, tuple(
            jax.ShapeDtypeStruct(x.shape, dtype) for x in leaves), tree,
            stream)

    n_params = sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(
        params))
    room = (REMAT_FILL * device_memory_limit()[0]
            - n_params * FOOTPRINT_PER_PARAMETER
            - math.prod(stream.shape) * FOOTPRINT_PER_STREAM_VALUE)
    return leading_remat(len(layers), (
        kept_bytes(*layer) for layer in reversed(layers)), room)


def blocks(model, block, tokens_shape) -> list:
    """``model``'s blocks (``model._layers()``: ``((name, args of block),
    ...)`` in order), made inside its ``__call__``: the first
    ``model.remat_blocks`` of them wrapped in ``nn.remat``; every one
    while the parameters are made, as there are none yet to count."""
    layers = model._layers()
    remat = len(layers) if model.is_initializing() else model.remat_blocks(
        model.variables["params"], tokens_shape)
    wrapped = nn.remat(block)
    return [(wrapped if i < remat else block)(*args, name=name)
            for i, (name, args) in enumerate(layers)]


# --- flax: parameters --------------------------------------------------------


def normal(fan_in: float):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.normal(key, shape, dtype) / math.sqrt(fan_in)

    return init


class Norm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return _rms_norm(x, scale, self.eps)


class Params(nn.Module):
    """A bag of named tensors: the mixers and the expert layer are plain
    functions of a dict, shared with nothing else."""

    shapes: Any  # ((name, shape, fan_in or "ones"/"zeros"), ...)

    @nn.compact
    def __call__(self):
        out = {}
        for name, shape, kind in self.shapes:
            init = {
                "ones": nn.initializers.ones, "zeros": nn.initializers.zeros,
            }.get(kind) or normal(kind)
            out[name] = self.param(name, init, tuple(shape))
        return out


# --- a model file's scopes ---------------------------------------------------


def scope_lookup(scopes: dict):
    """``scope_of_line`` of a model's file from its ``{scope: (functions
    of that file, ...)}``: the named scope whose code holds a source line
    (a device trace gives each op the line it was traced from). Code
    outside the scopes' functions (the blocks' norms and residual adds)
    has none."""

    @functools.cache
    def lines() -> tuple:
        out = []
        for scope, functions in scopes.items():
            for fn in functions:
                source, first = inspect.getsourcelines(fn)
                out.append((first, first + len(source) - 1, scope))
        return tuple(out)

    def scope_of_line(line: int) -> str | None:
        for first, last, scope in lines():
            if first <= line <= last:
                return scope
        return None

    return scope_of_line
