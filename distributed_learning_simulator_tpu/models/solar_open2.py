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
import inspect
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_learning_simulator_tpu.models.traced_helpers import (
    HIGHEST,
    causal_conv as _causal_conv,
    ein as _ein,
    l2_norm as _l2_norm,
    mm as _mm,
    rms_norm as _rms_norm,
)

#: Positions per chunk of the delta rule and per sub-block of a chunk.
KDA_CHUNK = 64
KDA_SUB = 16
#: Queries per block of the softmax attention (its scores are
#: materialised a block at a time).
GQA_QUERY_BLOCK = 512
#: Slots a held expert has, as a multiple of its even share of a batch's
#: assignments (rounded up to 128 rows, 8 below 128); beyond it the layer
#: falls back to every token (moe_experts).
EXPERT_CAPACITY_FACTOR = 2.0


def _normal(fan_in: int):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.normal(key, shape, dtype) / math.sqrt(fan_in)

    return init


# --- the gated delta rule, chunked -----------------------------------------


def _intra_chunk(qc, kc, gc, sub: int):
    """Decay-weighted products inside one chunk, all f32.

    ``A[t, s] = sum_d k_t k_s exp(g_t - g_s)`` for ``s < t`` and ``P[t, s]
    = sum_d q_t k_s exp(g_t - g_s)`` for ``s <= t``, ``g`` the log decay
    summed from the chunk's start (falling with ``t``). ``exp(-g_s)``
    alone overflows under a strong decay, so no factor above 1 is ever
    formed: between sub-blocks of ``sub`` positions both operands are
    scaled against the decay at the later block's start; inside a
    sub-block the ``[sub, sub, d]`` differences are exponentiated
    directly. Shapes ``[..., C, d]`` -> ``[..., C, C]``.
    """
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
                       chunk: int = KDA_CHUNK, sub: int = KDA_SUB):
    """``S_t = (I - b_t k_t k_t^T) Diag(a_t) S_(t-1) + b_t k_t v_t^T``,
    ``o_t = S_t^T q_t``, ``S_0 = 0``, in chunks of ``chunk`` positions.

    Inside a chunk the rank-one updates are folded into one triangular
    system (the WY / UT-transform form): with ``u_t = b_t (v_t - S_(t-1)^T
    Diag(a_t) k_t)`` the state is ``S_t = Diag(a_t) S_(t-1) + k_t u_t^T``,
    and over a chunk ``(I + Diag(b) A) U = Diag(b) (V - K+ S_0)`` with
    ``A`` of :func:`_intra_chunk` and ``K+ = K exp(g)``. The triangular
    inverse and ``A``, ``P`` are f32; the products with the ``[d, d]``
    state multiply in ``dtype`` and accumulate f32; the state is carried
    between chunks by ``lax.scan`` in f32.

    ``q, k, v, log_decay`` ``[B, T, H, d]`` f32, ``beta`` ``[B, T, H]``;
    returns ``[B, T, H, d]`` f32. ``T`` need not be a multiple of
    ``chunk``: padded positions have ``k = v = 0`` and no decay, so they
    leave the state as it is.
    """
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
              query_block: int = GQA_QUERY_BLOCK):
    """Causal softmax attention without positions over the query heads
    held here and their key/value heads, gated per output channel."""
    with jax.named_scope("gqa"):
        B, T, _ = x.shape
        group = heads // kv_heads
        q = _mm(x, p["q"], dtype).reshape(B, T, kv_heads, group, head_dim)
        k = _mm(x, p["k"], dtype).reshape(B, T, kv_heads, head_dim)
        v = _mm(x, p["v"], dtype).reshape(B, T, kv_heads, head_dim)
        block = query_block if T % query_block == 0 else T

        @jax.checkpoint
        def attend(args):
            # One block of queries against every key: the scores of a
            # block are all that is ever live, forward or backward.
            q_blk, first = args  # [B, block, kv, group, d]
            s = _ein("btkgd,bskd->bkgts", q_blk, k, dtype) / math.sqrt(
                head_dim)
            visible = (
                first + jnp.arange(block)[:, None] >= jnp.arange(T)[None, :]
            )
            w = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
            return _ein("bkgts,bskd->btkgd", w, v, dtype)

        blocks = jnp.moveaxis(
            q.reshape(B, T // block, block, kv_heads, group, head_dim), 1, 0
        )
        o = jax.lax.map(
            attend, (blocks, jnp.arange(T // block) * block)
        )  # [T / block, B, block, kv, group, d]
        o = jnp.moveaxis(o, 0, 1)
        gate = jax.nn.sigmoid(_mm(x, p["g"], dtype))
        return _mm(o.reshape(B, T, heads * head_dim) * gate, p["o"], dtype)


def moe_route(p, x, *, top_k: int, expert_offset: int, experts_held: int):
    """Score every expert of the layer (f32: a near-tie between the
    ``top_k``-th and the next score decides where a token goes), choose
    ``top_k``, normalise their scores; ``x`` ``[N, D]``. Returns the
    combine weight of each held expert for each token ``[N, held]`` (0
    where the token did not choose it)."""
    with jax.named_scope("moe/route"):
        scores = jax.nn.sigmoid(_mm(x, p["router"], jnp.float32))
        top, index = jax.lax.top_k(scores, top_k)
        weight = top / jnp.sum(top, -1, keepdims=True)
        local = index - expert_offset  # [N, top_k]
        held = jnp.arange(experts_held)
        return jnp.sum(
            weight[..., None] * (local[..., None] == held), axis=-2
        )


def moe_experts(p, x, combine, *, capacity: int, dtype):
    """The held experts' part of the layer for the tokens routed to them.

    Each held expert gathers the tokens that chose it into ``capacity``
    slots and the experts run as one grouped product over ``[held,
    capacity]``: the work follows the tokens routed here, not the tokens
    in the batch. No token is dropped: when any held expert is chosen by
    more than ``capacity`` tokens the layer computes every held expert
    over every token with the combine weights as a mask (``lax.cond``;
    counted in ``overflow``). Returns ``(y [N, D], load [held], overflow)``.
    """
    with jax.named_scope("moe/experts"):
        n_tokens = x.shape[0]
        chosen = combine > 0  # [N, held]
        load = jnp.sum(chosen, axis=0).astype(jnp.int32)

        def experts(xg):  # [held, rows, D]
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
            y = experts(jnp.take(x, index, axis=0)) * weight[..., None]
            return jnp.zeros((n_tokens, x.shape[-1]), jnp.float32).at[
                index.reshape(-1)
            ].add(y.reshape(-1, y.shape[-1]))

        def every_token(_):
            # One held expert after another over every token, the
            # combine weight as the mask.
            @jax.checkpoint
            def one(y, expert):
                gate, up, down, weight = expert
                hidden = jax.nn.silu(_mm(x, gate, dtype)) * _mm(x, up, dtype)
                return y + _mm(hidden, down, dtype) * weight[:, None], None

            y, _ = jax.lax.scan(
                one, jnp.zeros((n_tokens, x.shape[-1]), jnp.float32),
                (p["gate"], p["up"], p["down"], combine.T),
            )
            return y

        overflow = jnp.any(load > capacity)
        if capacity >= n_tokens:
            return every_token(None), load, overflow
        return jax.lax.cond(overflow, every_token, grouped, None), load, \
            overflow


def moe_shared(p, x, *, dtype):
    with jax.named_scope("moe/shared"):
        hidden = jax.nn.silu(_mm(x, p["shared_gate"], dtype)) * _mm(
            x, p["shared_up"], dtype
        )
        return _mm(hidden, p["shared_down"], dtype)


def lm_head(kernel, x, *, dtype):
    """Logits over the rows of the vocabulary held, accumulated f32 and
    kept in ``dtype``: at ``[tokens, 24576]`` they are the largest
    activation of the step, and the loss takes its softmax in f32.
    The model does not hand them to the engine: :func:`head_nll`."""
    with jax.named_scope("lm_head"):
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
    with jax.named_scope("lm_head"):
        return _head_nll(
            kernel.astype(dtype), x.astype(dtype), targets, weight)


_SCOPES = {
    "kda": (kda_mixer, chunked_delta_rule, _intra_chunk,
            _unit_lower_inverse),
    "gqa": (gqa_mixer,),
    "moe/route": (moe_route,),
    "moe/experts": (moe_experts,),
    "moe/shared": (moe_shared,),
    "lm_head": (lm_head, head_nll, _head_loss, _head_nll,
                _head_nll_forward, _head_nll_backward),
}


@functools.cache
def _scope_lines() -> tuple:
    """``(first line, last line, scope)`` of every function above."""
    out = []
    for scope, functions in _SCOPES.items():
        for fn in functions:
            lines, first = inspect.getsourcelines(fn)
            out.append((first, first + len(lines) - 1, scope))
    return tuple(out)


def scope_of_line(line: int) -> str | None:
    """The named scope whose code holds source line ``line`` of this
    file (a device trace gives each op the line it was traced from).
    Code outside the scopes' functions (the blocks' norms and residual
    adds) has none."""
    for first, last, scope in _scope_lines():
        if first <= line <= last:
            return scope
    return None


# --- the module -------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
class UnmadeLogits:
    """What the model hands on in place of logits: the head's kernel and
    its normed input. The engine's loss asks it for :meth:`weighted_nll`
    (:func:`head_nll`: no logits leave the head); whoever wants the
    logits themselves asks for :meth:`logits`."""

    def __init__(self, kernel, x, dtype):
        self.kernel, self.x, self.dtype = kernel, x, dtype

    def logits(self):
        return lm_head(self.kernel, self.x, dtype=self.dtype)

    def weighted_nll(self, targets, weight):
        return head_nll(self.kernel, self.x, targets, weight,
                        dtype=self.dtype)

    def tree_flatten(self):
        return (self.kernel, self.x), self.dtype

    @classmethod
    def tree_unflatten(cls, dtype, children):
        return cls(*children, dtype)


class _Norm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return _rms_norm(x, scale, self.eps)


class _Params(nn.Module):
    """A bag of named tensors: the mixers and the expert layer are plain
    functions of a dict, shared with nothing else."""

    shapes: Any  # ((name, shape, fan_in or "ones"/"zeros"), ...)

    @nn.compact
    def __call__(self):
        out = {}
        for name, shape, kind in self.shapes:
            init = {
                "ones": nn.initializers.ones, "zeros": nn.initializers.zeros,
            }.get(kind) or _normal(kind)
            out[name] = self.param(name, init, tuple(shape))
        return out


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
        even = n_tokens * self.num_experts_per_tok / self.n_routed_experts
        rows = math.ceil(even * EXPERT_CAPACITY_FACTOR)
        tile = 128 if rows >= 128 else 8
        return min(n_tokens, math.ceil(rows / tile) * tile)


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
        x = _Norm(c.rms_norm_eps, name="mixer_norm")(h)
        if self.is_gqa:
            kv = c.kv_heads_held
            p = _Params((
                ("q", (D, H * hd), D), ("k", (D, kv * hd), D),
                ("v", (D, kv * hd), D), ("g", (D, H * hd), D),
                ("o", (H * hd, D), back),
            ), name="gqa")()
            h = h + gqa_mixer(p, x, heads=H, kv_heads=kv, head_dim=hd,
                              dtype=dtype)
        else:
            R, taps = c.gate_rank, c.short_conv_kernel_size
            p = _Params((
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
        x = _Norm(c.rms_norm_eps, name="moe_norm")(h)
        Eh = c.experts_held
        p = _Params((
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
    init_positions = KDA_CHUNK
    #: The head makes its loss and both its gradients itself and hands
    #: on no logits (head_nll); the recorder's counter of the same name.
    head_backward_tied = True

    @nn.compact
    def __call__(self, tokens):
        if self.vocab_rows not in (None, self.num_classes):
            raise ValueError(
                f"vocab_rows={self.vocab_rows} but the dataset draws its "
                f"ids from {self.num_classes}"
            )
        c = self.share
        D, vocab = c.hidden_size, self.num_classes
        table = _Params((("table", (vocab, D), 1),), name="embed")()["table"]
        h = jnp.take(table, tokens, axis=0).astype(jnp.float32)
        block = nn.remat(_Block)
        loads, overflows = [], []
        for layer in range(c.num_hidden_layers):
            h, (load, overflow) = block(
                c, layer in c.gqa_layers, name=f"layer_{layer}"
            )(h)
            loads.append(load)
            overflows.append(overflow)
        x = _Norm(c.rms_norm_eps, name="final_norm")(h)
        kernel = _Params(
            (("kernel", (D, vocab), D),), name="lm_head")()["kernel"]
        head = UnmadeLogits(kernel, x, jnp.dtype(c.dtype))
        load = jnp.stack(loads)  # [layers, held]
        counts = {
            "moe_local_assignments": jnp.sum(load, axis=1),
            "moe_routed_tokens": jnp.full(
                (c.num_hidden_layers,), tokens.size, jnp.int32),
            "moe_overflows": jnp.stack(overflows).astype(jnp.int32),
            "moe_expert_load": load,
        }
        return head, counts


def solar_open2(num_classes: int, vocab_rows: int | None = None, **share):
    """The registry's constructor: ``--model_args`` gives the share
    (``heads_held``, ``experts_held``, ``vocab_rows``, ...) and any
    published size a smaller preset changes, flat."""
    if "gqa_layers" in share:
        share["gqa_layers"] = tuple(share["gqa_layers"])
    return SolarOpen2(num_classes=num_classes, share=Share(**share),
                      vocab_rows=vocab_rows)
