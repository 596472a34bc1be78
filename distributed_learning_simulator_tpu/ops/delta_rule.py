"""The gated delta rule with ONE decay a head (Gated DeltaNet), forward and
backward, as Pallas TPU kernels over the chunk form of
``models/lm_parts.chunked_delta_rule``.

Per sequence and head, in chunks of :data:`CHUNK` positions, with ``g``
the log decay summed from the chunk's start, ``G[t, s] = exp(g_t - g_s)``
for ``s <= t``, ``e = exp(g)`` and ``S`` the ``[d_k, d_v]`` state at the
chunk's start::

    A  = (K K^T) * G, strictly lower        P  = (Q K^T) * G
    T  = (I + Diag(b) A)^-1 Diag(b)         W  = T (e K),   U0 = T V
    U  = U0 - W S                           O  = (e Q) S + P U
    S' = exp(g_last) S + (exp(g_last - g) K)^T U

the same arithmetic as the XLA form: ``A``, ``P`` and the triangular
inverse (``I + L + L^2 + ...`` by doubling, ``lm_parts._unit_lower_inverse``)
f32 at ``HIGHEST``; the products with ``W``, ``U0`` and the state multiply
in ``dtype`` and accumulate f32; the state is f32.

* :func:`kernel_forward`: a grid over (sequence, block of heads, chunk),
  the chunk axis sequential with the heads' states carried in VMEM
  scratch. A block's heads are worked in slabs of :data:`STACK`, their
  chunks stacked on the rows: the ``[C, C]`` matrices of a slab are the
  diagonal blocks of one matrix (what lies between two heads is made and
  masked away), the products with a head's state are the head's own. A
  chunk's ``A``, ``P``, inverse, ``W`` and ``U`` never leave VMEM; the
  output and the state at each chunk's start (``[B, H, n, d_k, d_v]``
  f32, for the backward) are written out.
* :func:`kernel_backward`: the chunks in reverse, the adjoint of the state
  carried in VMEM scratch. Each chunk's local quantities are made again
  from ``q``, ``k``, ``v``, ``g``, ``b`` and the saved state; it returns
  ``dq``, ``dk``, ``dv`` and the gradients of ``g`` and ``b``. No forward
  pass of the XLA form runs.

The kernels read a head's ``q``, ``k``, ``v`` as ``[B, H, T, d]`` and a
slab's ``g`` and ``b`` as rows ``[B, H / STACK, n, STACK * CHUNK]``, one
row a chunk: a column (``g_t`` down the rows) is made from a row in VMEM
by a masked lane sum (:func:`_column`), so no array with a last dimension
of 1 is ever laid out in HBM.

:func:`delta_rule` is the rule of ``[B, T, H, d]`` inputs with both kernels
as its ``jax.custom_vjp``; ``lm_parts.chunked_delta_rule`` chooses it by
what it can observe (one decay a head, :func:`kernel_applies`, a program
lowered for a TPU) and keeps its XLA form everywhere else. No flag.

Like ``models/lm_parts.py`` this file is registered with JAX as not being
user code: an op made here, the kernels' calls among them, carries the
line of its caller in a model's file, whose scopes a device trace is read
by.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend import source_info_util

source_info_util.register_exclusion(__file__)

HIGHEST = jax.lax.Precision.HIGHEST
#: Positions a chunk: ``lm_parts.DELTA_CHUNK``, the XLA form's.
CHUNK = 64
#: Heads stacked on the rows of one slab: their ``[C, C]`` matrices are
#: the diagonal blocks of one ``[STACK * C, STACK * C]`` matrix, so each
#: product of the chunk-local work (``A``, ``P``, the inverse, ``W``,
#: ``U0``, ``P U``) fills 128 rows and lanes of the MXU, not 64.
STACK = 2
#: The most heads a grid step of the kernels works (the widest divisor of
#: the heads up to it in whole slabs). Read on the chip at the Olmo cell's
#: shapes (the rule forward / forward + backward, ms): one head a slab
#: 5.38 / 12.20 at 1 head a step, 5.05 / 11.49 at 6; two heads a slab
#: 4.41 / 9.85 at 2, 4.25 / 9.44 at 6, 4.22 / 9.49 at 10 (PERF.md § 6).
HEAD_BLOCK = 6
#: Names the kernels carry in a device trace (``benchmark/metrics``).
FORWARD_NAME = "delta_rule_forward"
BACKWARD_NAME = "delta_rule_backward"

_NN = ((1,), (0,))  # a @ b
_NT = ((1,), (1,))  # a @ b^T
_TN = ((0,), (0,))  # a^T @ b


def kernel_applies(T: int, d_k: int, d_v: int, chunk: int = CHUNK) -> bool:
    """Whether the kernels take sequences of ``T`` positions in chunks of
    ``chunk`` over keys of ``d_k`` and values of ``d_v``, by shapes alone:
    the XLA form's chunk, whole chunks, head dims in whole bf16 sublane
    tiles and a state that leaves room in VMEM. Whether they RUN is also
    the lowering platform's to say (``lm_parts.chunked_delta_rule``)."""
    return (chunk == CHUNK and T % CHUNK == 0 and d_k % 16 == 0
            and d_v % 16 == 0 and d_k * d_v <= 256 * 256)


def stack(H: int) -> int:
    """Heads stacked on the rows of one slab: :data:`STACK` where it
    divides ``H``, else 1."""
    return STACK if H % STACK == 0 else 1


def head_block(H: int) -> int:
    """Heads a grid step: the widest divisor of ``H`` up to
    :data:`HEAD_BLOCK` that is whole slabs (:func:`stack`)."""
    return max(h for h in range(1, min(H, HEAD_BLOCK) + 1)
               if H % h == 0 and h % stack(H) == 0)


# --- what both kernels compute of a chunk ---------------------------------------


def _dot(a, b, dims, dtype):
    """``a``, ``b`` contracted over ``dims``, multiplied in ``dtype`` and
    accumulated f32 (``HIGHEST`` where ``dtype`` is f32)."""
    precision = HIGHEST if dtype == jnp.float32 else None
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype), (dims, ((), ())),
        precision=precision, preferred_element_type=jnp.float32)


def _hi(a, b, dims):
    """An f32 product at ``HIGHEST``."""
    return _dot(a, b, dims, jnp.float32)


def _column(row, eye):
    """``[1, R]`` -> ``[R, 1]``: the diagonal matrix of ``row``, summed
    across the lanes."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(column, eye):
    """``[R, 1]`` -> ``[1, R]``."""
    return jnp.sum(jnp.where(eye, column, 0.0), axis=0, keepdims=True)


def _slab(ref, j: int, heads: int):
    """Heads ``j * heads ...`` of a ``[hb, C, d]`` block as one ``[heads *
    C, d]`` slab."""
    block = ref[j * heads:(j + 1) * heads]
    return block.reshape((-1,) + block.shape[2:])


def _stacked(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _unit_lower_inverse(L, eye, C: int):
    """``(I - L)^-1`` for ``L`` ``[R, R]`` strictly lower-triangular in
    diagonal blocks of ``C``, ``prod_j (I + L^(2^j))`` to ``L^C = 0``:
    ``lm_parts._unit_lower_inverse`` of each block."""
    inv = eye + L
    power = L
    for _ in range(max(0, math.ceil(math.log2(C)) - 1)):
        power = _hi(power, power, _NN)
        inv = _hi(inv, eye + power, _NN)
    return inv


def _chunk(q, k, g_row, b_row, heads: int):
    """A chunk's local quantities for ``heads`` heads stacked on the rows:
    ``q``, ``k`` ``[R, d_k]`` (``R = heads * C``), the rows ``g``, ``b``
    ``[1, R]``. The ``[R, R]`` matrices are block-diagonal, a head a
    block (a product across two heads is made and masked away); columns
    are ``[R, 1]``, ``gamma`` the decay over the row's head's chunk."""
    R = q.shape[0]
    C = R // heads
    t = jax.lax.broadcasted_iota(jnp.int32, (R, R), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (R, R), 1)
    diag = t == s
    head_start = t - t % C
    same = (s >= head_start) & (s < head_start + C)
    g = _column(g_row, diag)
    b = _column(b_row, diag)
    strict = (t > s) & same
    decay = jnp.exp(jnp.where((t >= s) & same, g - g_row, -jnp.inf))
    kk = _hi(k, k, _NT)
    qk = _hi(q, k, _NT)
    A = jnp.where(strict, kk * decay, 0.0)
    inv = _unit_lower_inverse(-b * A, diag.astype(jnp.float32), C)
    total = jnp.sum(jnp.where(s == head_start + C - 1, g_row, 0.0), axis=1,
                    keepdims=True)
    return dict(
        diag=diag, strict=strict, decay=decay, kk=kk, qk=qk, A=A,
        P=qk * decay, inv=inv, solve=inv * b_row, b=b, e=jnp.exp(g),
        f=jnp.exp(total - g), gamma=jnp.exp(total))


# --- the kernels ------------------------------------------------------------------


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s0_ref, s_scr,
                    *, dtype, heads):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    C = q_ref.shape[1]
    for j in range(q_ref.shape[0] // heads):
        q, k = _slab(q_ref, j, heads), _slab(k_ref, j, heads)
        x = _chunk(q, k, g_ref[j, pl.ds(c, 1), :], b_ref[j, pl.ds(c, 1), :],
                   heads)
        W = _dot(x["solve"], k * x["e"], _NN, dtype)
        U0 = _dot(x["solve"], _slab(v_ref, j, heads), _NN, dtype)
        states, U = [], []
        for i in range(heads):
            rows = slice(i * C, (i + 1) * C)
            S = s_scr[j * heads + i]
            s0_ref[j * heads + i] = S
            states.append(S)
            U.append(U0[rows] - _dot(W[rows], S, _NN, dtype))
        PU = _dot(x["P"], _stacked(U), _NN, dtype)
        q_in, k_out = q * x["e"], k * x["f"]
        for i, S in enumerate(states):
            rows = slice(i * C, (i + 1) * C)
            o_ref[j * heads + i] = _dot(q_in[rows], S, _NN, dtype) + PU[rows]
            s_scr[j * heads + i] = S * x["gamma"][i * C:i * C + 1] + _dot(
                k_out[rows], U[i], _TN, dtype)


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s0_ref, do_ref,
                     dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_scr,
                     *, dtype, heads):
    step = pl.program_id(2)
    c = pl.num_programs(2) - 1 - step

    @pl.when(step == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    C = q_ref.shape[1]
    for j in range(q_ref.shape[0] // heads):
        q, k = _slab(q_ref, j, heads), _slab(k_ref, j, heads)
        v, dO = _slab(v_ref, j, heads), _slab(do_ref, j, heads)
        b_row = b_ref[j, pl.ds(c, 1), :]
        x = _chunk(q, k, g_ref[j, pl.ds(c, 1), :], b_row, heads)
        e, f, solve, gamma = x["e"], x["f"], x["solve"], x["gamma"]
        k_in, q_in, k_out = k * e, q * e, k * f
        W = _dot(solve, k_in, _NN, dtype)
        U0 = _dot(solve, v, _NN, dtype)
        PdO = _dot(x["P"], dO, _TN, dtype)
        # The state before the chunk (S) and the adjoint after it (dS), a
        # head at a time; O = q_in S + P U, S' = gamma S + k_out^T U, U =
        # U0 - W S: back to U, S, q_in, k_out, W and gamma.
        U, dU, dq_in, dk_out, dW, d_gamma = [], [], [], [], [], []
        for i in range(heads):
            h, rows = j * heads + i, slice(i * C, (i + 1) * C)
            S, dS = s0_ref[h], ds_scr[h]
            U.append(U0[rows] - _dot(W[rows], S, _NN, dtype))
            dU.append(PdO[rows] + _dot(k_out[rows], dS, _NN, dtype))
            dq_in.append(_dot(dO[rows], S, _NT, dtype))
            dk_out.append(_dot(U[i], dS, _NT, dtype))
            dW.append(-_dot(dU[i], S, _NT, dtype))
            d_gamma.append(jnp.sum(jnp.sum(dS * S, axis=1, keepdims=True),
                                   axis=0, keepdims=True))
            ds_scr[h] = dS * gamma[i * C:i * C + 1] + _dot(
                q_in[rows], dO[rows], _TN, dtype) - _dot(
                    W[rows], dU[i], _TN, dtype)
        U, dU, dq_in, dk_out, dW = (
            _stacked(a) for a in (U, dU, dq_in, dk_out, dW))
        dP = _dot(dO, U, _NT, dtype)
        # W = T k_in, U0 = T V: back to T, k_in, V.
        dT = _dot(dW, k_in, _NT, dtype) + _dot(dU, v, _NT, dtype)
        dk_in = _dot(solve, dW, _TN, dtype)
        dv = _dot(solve, dU, _TN, dtype)
        # T = inv Diag(b), inv = (I + Diag(b) A)^-1: back to b and A.
        inv = x["inv"]
        dM = -_hi(_hi(inv, dT * b_row, _TN), inv, _NT)
        dA = jnp.where(x["strict"], dM * x["b"], 0.0)
        db_col = jnp.sum(dM * x["A"], axis=1, keepdims=True)
        db_row = jnp.sum(dT * inv, axis=0, keepdims=True)
        # A = (k k^T) G strictly lower, P = (q k^T) G: back to q, k, G.
        decay = x["decay"]
        d_kk, d_qk = dA * decay, dP * decay
        R = (dA * x["kk"] + dP * x["qk"]) * decay  # dG G: back to g
        dq = _hi(d_qk, k, _NN) + dq_in * e
        dk = (_hi(d_kk, k, _NN) + _hi(d_kk, k, _TN) + _hi(d_qk, q, _TN)
              + dk_in * e + dk_out * f)
        # e = exp(g), f = exp(total - g), gamma = exp(total), total the
        # head's last g.
        d_f = f * jnp.sum(dk_out * k, axis=1, keepdims=True)
        dg_col = (jnp.sum(R, axis=1, keepdims=True)
                  + e * jnp.sum(dk_in * k + dq_in * q, axis=1, keepdims=True)
                  - d_f)
        row = jax.lax.broadcasted_iota(jnp.int32, dg_col.shape, 0)
        for i in range(heads):
            h, rows = j * heads + i, slice(i * C, (i + 1) * C)
            d_total = jnp.sum(d_f[rows], axis=0, keepdims=True) + (
                gamma[i * C:i * C + 1] * d_gamma[i])
            dg_col = dg_col + jnp.where(row == i * C + C - 1, d_total, 0.0)
            dq_ref[h] = dq[rows]
            dk_ref[h] = dk[rows]
            dv_ref[h] = dv[rows].astype(dv_ref.dtype)
        dg_ref[j, pl.ds(c, 1), :] = _row(dg_col, x["diag"]) - jnp.sum(
            R, axis=0, keepdims=True)
        db_ref[j, pl.ds(c, 1), :] = _row(db_col, x["diag"]) + db_row


def _vmem_limit(blocks: int, scratch: int) -> int:
    """VMEM for the blocks (double-buffered) and the scratch, and 16 MiB
    for the chunk's own matrices and the compiler's."""
    return min(2 * blocks + scratch + (16 << 20), 100 << 20)


def _specs(q, v, reverse: bool):
    B, H, T, dk = q.shape
    dv, n, hb, heads = v.shape[-1], T // CHUNK, head_block(H), stack(H)

    def chunk(i):
        return n - 1 - i if reverse else i

    def seq(d):
        return pl.BlockSpec((None, hb, CHUNK, d),
                            lambda b, h, i: (b, h, chunk(i), 0))

    rows = pl.BlockSpec((None, hb // heads, n, heads * CHUNK),
                        lambda b, h, i: (b, h, 0, 0))
    state = pl.BlockSpec((None, hb, None, dk, dv),
                         lambda b, h, i: (b, h, chunk(i), 0, 0))
    sizes = dict(seq_k=hb * CHUNK * dk * 4, seq_v=hb * CHUNK * dv * 4,
                 rows=hb * n * 128 * 4, state=hb * dk * dv * 4)
    return (B, H // hb, n), heads, seq, rows, state, sizes


def kernel_forward(q, k, v, g, b, *, dtype, interpret: bool = False):
    """``(o, states)`` of ``q``, ``k`` ``[B, H, T, d_k]`` f32, ``v``
    ``[B, H, T, d_v]`` in ``dtype`` (the one product it enters rounds it
    there) and the rows ``g`` (the log decay summed from each chunk's
    start), ``b`` ``[B, H / s, n, s * CHUNK]`` f32 (:func:`stack` ``s``
    heads' rows of a chunk side by side): ``o`` ``[B, H, T, d_v]`` f32
    and ``states[b, h, i]`` the ``[d_k, d_v]`` state before chunk ``i``.
    Shapes must satisfy :func:`kernel_applies`. ``interpret`` runs the
    kernel in Pallas's interpreter (the CPU tests)."""
    B, H, T, dk = q.shape
    dv, f32 = v.shape[-1], jnp.float32
    grid, heads, seq, rows, state, sz = _specs(q, v, reverse=False)
    return pl.pallas_call(
        functools.partial(_forward_kernel, dtype=dtype, heads=heads),
        grid=grid,
        in_specs=[seq(dk), seq(dk), seq(dv), rows, rows],
        out_specs=[seq(dv), state],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, dv), f32),
                   jax.ShapeDtypeStruct((B, H, T // CHUNK, dk, dv), f32)],
        scratch_shapes=[pltpu.VMEM((H // grid[1], dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                2 * sz["seq_k"] + 2 * sz["seq_v"] + 2 * sz["rows"]
                + sz["state"], sz["state"])),
        interpret=interpret,
        name=FORWARD_NAME,
    )(q, k, v, g, b)


def kernel_backward(q, k, v, g, b, states, do, *, dtype,
                    interpret: bool = False):
    """The gradients ``(dq, dk, dv, dg, db)`` of :func:`kernel_forward`'s
    ``o`` against ``do`` ``[B, H, T, d_v]`` (in ``dtype``: every product
    it enters rounds it there), from the saved ``states``; ``dv`` in
    ``v``'s type, as the XLA form's products round it, ``dg``, ``db`` as
    rows like ``g``, ``b``."""
    B, H, T, dk = q.shape
    dv, f32 = v.shape[-1], jnp.float32
    grid, heads, seq, rows, state, sz = _specs(q, v, reverse=True)
    return pl.pallas_call(
        functools.partial(_backward_kernel, dtype=dtype, heads=heads),
        grid=grid,
        in_specs=[seq(dk), seq(dk), seq(dv), rows, rows, state, seq(dv)],
        out_specs=[seq(dk), seq(dk), seq(dv), rows, rows],
        out_shape=[jax.ShapeDtypeStruct(q.shape, f32),
                   jax.ShapeDtypeStruct(k.shape, f32),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, f32),
                   jax.ShapeDtypeStruct(b.shape, f32)],
        scratch_shapes=[pltpu.VMEM((H // grid[1], dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                4 * sz["seq_k"] + 3 * sz["seq_v"] + 4 * sz["rows"]
                + sz["state"], sz["state"])),
        interpret=interpret,
        name=BACKWARD_NAME,
    )(q, k, v, g, b, states, do)


# --- the rule with its gradient ---------------------------------------------------


@functools.lru_cache(maxsize=None)
def _rule(dtype, interpret: bool):
    """The kernels as one ``jax.custom_vjp`` of ``(q, k, v, g, b)`` in the
    kernels' layout; the residuals are the inputs and the saved states."""

    @jax.custom_vjp
    def rule(q, k, v, g, b):
        return kernel_forward(q, k, v, g, b, dtype=dtype,
                              interpret=interpret)[0]

    def rule_forward(q, k, v, g, b):
        o, states = kernel_forward(q, k, v, g, b, dtype=dtype,
                                   interpret=interpret)
        return o, (q, k, v, g, b, states)

    def rule_backward(residuals, do):
        return kernel_backward(*residuals, do.astype(dtype), dtype=dtype,
                               interpret=interpret)

    rule.defvjp(rule_forward, rule_backward)
    return rule


def delta_rule(q, k, v, log_decay, beta, *, dtype, interpret: bool = False):
    """``lm_parts.chunked_delta_rule`` for one decay a head through the
    kernels: ``q``, ``k`` ``[B, T, H, d_k]``, ``v`` ``[B, T, H, d_v]``,
    ``log_decay`` ``[B, T, H, 1]`` or ``[B, T, H]``, ``beta`` ``[B, T,
    H]``; returns ``[B, T, H, d_v]`` f32. Shapes must satisfy
    :func:`kernel_applies`. The decay's sum from each chunk's start and
    the layout changes are XLA's (and so are their gradients)."""
    B, T, H, _ = q.shape
    n = T // CHUNK
    f32 = jnp.float32

    def heads_first(a):  # [B, T, H, d] -> [B, H, T, d]
        return jnp.transpose(a.astype(f32), (0, 2, 1, 3))

    def chunks(a):  # [B, T, H] -> [B, H, n, CHUNK]
        return jnp.transpose(a.astype(f32).reshape(B, n, CHUNK, H),
                             (0, 3, 1, 2))

    def rows(a):  # [B, H, n, CHUNK] -> [B, H / s, n, s * CHUNK]
        a = a.reshape(B, H // s, s, n, CHUNK)
        return jnp.swapaxes(a, 2, 3).reshape(B, H // s, n, s * CHUNK)

    s = stack(H)
    g = jnp.cumsum(chunks(log_decay.reshape(B, T, H)), axis=-1)
    o = _rule(jnp.dtype(dtype), interpret)(
        heads_first(q), heads_first(k), heads_first(v).astype(dtype),
        rows(g), rows(chunks(beta)))
    return jnp.transpose(o, (0, 2, 1, 3))
