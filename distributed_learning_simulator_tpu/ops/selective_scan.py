"""The selective scan of a Mamba-1 mixer (Gu & Dao 2023, arXiv:2312.00752),
forward and backward, in two forms with one set of chunk boundaries.

Per sequence, channel ``c`` and state ``n``::

    s_t[c, n] = exp(delta_t[c] A[c, n]) s_(t-1)[c, n] + delta_t[c] B_t[n] x_t[c]
    y_t[c]    = sum_n s_t[c, n] C_t[n] + D[c] x_t[c]

with ``s_0 = 0``. Shapes: ``x``, ``delta`` ``[batch, T, E]``; ``A``
``[E, N]``; ``B``, ``C`` ``[batch, T, N]``; ``D`` ``[E]``; all arithmetic
f32. The decay depends on channel AND state, so the state is ``[N, E]`` a
sequence, worked elementwise (VPU and EUP: one ``exp`` a channel, state and
position); there is no matrix form for the MXU.

Time is cut into chunks of :data:`CHUNK` positions. The forward pass hands
its backward the state at each chunk's start (``[batch, T / CHUNK, N, E]``
f32); the backward walks the chunks in reverse, makes each chunk's states
again from the saved one, and runs the adjoint recurrence ``g_t = C_t dy_t
+ a_(t+1) g_(t+1)`` back through them. Nothing of size ``[T, N, E]`` is
ever written.

* :func:`kernel_forward` / :func:`kernel_backward`: Pallas TPU kernels, a
  grid over (sequence, block of channels, chunk), the chunk axis
  sequential with the ``[N, channels]`` state carried in VMEM scratch. A
  chunk's ``B`` and ``C`` arrive transposed, ``[N, CHUNK]``: one lane tile,
  from which a position's column is picked by a one-hot lane mask. The
  backward keeps the chunk's states in VMEM and returns ``dx``,
  ``d delta``, and per block of channels the partial sums of ``dB``,
  ``dC`` (over its channels) and ``dA``, ``dD`` (over the sequence),
  summed outside.
* :func:`xla_forward` / :func:`xla_backward`: the same arithmetic as
  ``lax.scan`` over the chunks and, inside one, over positions: the CPU's
  form, and any shape the kernel does not take.

:func:`selective_scan` chooses by what it can observe (as
``models/lm_parts.attention_core`` does): the XLA form where the shapes do
not suit the kernel (:func:`kernel_applies`), else ``lax.platform_dependent``
on the platform the program is lowered for. No flag.

Like ``models/lm_parts.py`` this file is registered with JAX as not being
user code: an op made here, the kernels' calls among them, carries the
line of its caller in a model's file, whose scopes a device trace is read
by.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend import source_info_util

source_info_util.register_exclusion(__file__)

#: Positions a chunk: the boundaries at which the forward pass saves the
#: state, in both forms. In the kernel a chunk's ``B`` and ``C`` are one
#: ``[N, 128]`` lane tile.
CHUNK = 128
#: The widest block of channels the kernels take a grid step (a multiple
#: of the 128 lanes that divides ``E``), and the positions written out a
#: trip of the kernels' loops over a chunk. Read on the chip at 16,384
#: positions over 1,280 channels: forward / backward 2.51 / 5.51 ms at
#: 640 channels and 4 positions, 1.97 / 4.28 at 1,280 and 8 (the fastest
#: of 256, 640, 1,280 x 2, 4, 8; PERF.md § 6).
CHANNEL_BLOCK = 1280
UNROLL = 8
#: Names the kernels carry in a device trace (``benchmark/metrics``).
FORWARD_NAME = "selective_scan_forward"
BACKWARD_NAME = "selective_scan_backward"


def _pad_time(a, pad):
    return jnp.pad(a, ((0, 0), (0, pad), (0, 0))) if pad else a


# --- the XLA form --------------------------------------------------------------


def _chunked(a, chunk):
    """``[batch, T, w]`` -> ``[T / chunk, chunk, batch, w]``."""
    b, t, w = a.shape
    return jnp.moveaxis(a.reshape(b, t // chunk, chunk, w), 0, 2)


def _unchunked(a, T):
    """:func:`_chunked`'s inverse, cut back to ``T`` positions."""
    n, chunk, b, w = a.shape
    return jnp.moveaxis(a, 2, 0).reshape(b, n * chunk, w)[:, :T]


def xla_forward(x, delta, A, B, C, D, chunk: int | None = None):
    """``(y, states)``: ``states[b, i]`` is the ``[N, E]`` state before
    chunk ``i``. ``T`` need not be a multiple of ``chunk``: padded
    positions have ``delta = 0`` and ``x = 0``, so they leave the state as
    it is."""
    T, chunk = x.shape[1], chunk or CHUNK
    pad = (-T) % chunk
    At = A.T.astype(jnp.float32)  # [N, E]

    def position(s, inp):
        x_t, d_t, b_t, c_t = inp  # [batch, E], [batch, E], [batch, N] x 2
        s = jnp.exp(d_t[:, None, :] * At) * s + (
            b_t[:, :, None] * (d_t * x_t)[:, None, :])
        return s, jnp.sum(s * c_t[:, :, None], axis=1)

    def one_chunk(s, inp):
        end, y = jax.lax.scan(position, s, inp)
        return end, (s, y)

    inputs = tuple(_chunked(_pad_time(a.astype(jnp.float32), pad), chunk)
                   for a in (x, delta, B, C))
    s0 = jnp.zeros((x.shape[0],) + At.shape, jnp.float32)
    _, (states, y) = jax.lax.scan(one_chunk, s0, inputs)
    y = _unchunked(y, T) + D.astype(jnp.float32) * x
    return y, jnp.moveaxis(states, 0, 1)


def xla_backward(x, delta, A, B, C, D, states, dy,
                 chunk: int | None = None):
    """The six gradients from the saved chunk states: each chunk's states
    made again from the one saved before it, the adjoint run back through
    them, chunk after chunk in reverse."""
    T, chunk = x.shape[1], chunk or CHUNK
    pad = (-T) % chunk
    At = A.T.astype(jnp.float32)
    Dv = D.astype(jnp.float32)

    def states_before(s0, inp):
        def position(s, i):
            x_t, d_t, b_t = i
            return jnp.exp(d_t[:, None, :] * At) * s + (
                b_t[:, :, None] * (d_t * x_t)[:, None, :]), s

        return jax.lax.scan(position, s0, inp)[1]

    def position_back(carry, inp):
        h, dA, dD = carry
        s_prev, x_t, d_t, b_t, c_t, dy_t = inp
        a = jnp.exp(d_t[:, None, :] * At)
        u = d_t * x_t
        s = a * s_prev + b_t[:, :, None] * u[:, None, :]
        g = h + c_t[:, :, None] * dy_t[:, None, :]
        d_c = jnp.sum(s * dy_t[:, None, :], axis=2)
        d_b = jnp.sum(g * u[:, None, :], axis=2)
        du = jnp.sum(g * b_t[:, :, None], axis=1)
        d_log = g * s_prev * a
        d_delta = jnp.sum(d_log * At, axis=1) + du * x_t
        dx = du * d_t + Dv * dy_t
        dA = dA + jnp.sum(d_log * d_t[:, None, :], axis=0)
        dD = dD + jnp.sum(x_t * dy_t, axis=0)
        return (a * g, dA, dD), (dx, d_delta, d_b, d_c)

    def one_chunk(carry, inp):
        s0, x_c, d_c, b_c, c_c, dy_c = inp
        s_prev = states_before(s0, (x_c, d_c, b_c))
        return jax.lax.scan(position_back, carry,
                            (s_prev, x_c, d_c, b_c, c_c, dy_c), reverse=True)

    inputs = tuple(_chunked(_pad_time(a.astype(jnp.float32), pad), chunk)
                   for a in (x, delta, B, C, dy))
    carry = (jnp.zeros_like(states[:, 0]), jnp.zeros_like(At),
             jnp.zeros_like(Dv))
    (_, dA, dD), (dx, d_delta, dB, dC) = jax.lax.scan(
        one_chunk, carry, (jnp.moveaxis(states, 1, 0),) + inputs,
        reverse=True)
    return (_unchunked(dx, T), _unchunked(d_delta, T), dA.T,
            _unchunked(dB, T), _unchunked(dC, T), dD)


# --- the Pallas kernels ---------------------------------------------------------


def channel_block(E: int) -> int:
    """The kernels' block of channels: the widest multiple of 128 that
    divides ``E`` and is at most :data:`CHANNEL_BLOCK`."""
    return max(w for w in range(128, min(E, CHANNEL_BLOCK) + 1, 128)
               if E % w == 0)


def kernel_applies(T: int, E: int) -> bool:
    """Whether the kernels take sequences of ``T`` positions over ``E``
    channels, by shapes alone: whole chunks, channels in whole lane tiles.
    Whether they RUN is also the lowering platform's to say
    (:func:`selective_scan`)."""
    return T % CHUNK == 0 and E % 128 == 0


def _loop(n: int, body, carry, unroll: int):
    """``lax.fori_loop(0, n, body, carry)`` with ``unroll`` steps written
    out a trip (Mosaic takes an unroll of 1 or of every step)."""
    def trip(i, carry):
        for j in range(unroll):
            carry = body(i * unroll + j, carry)
        return carry

    return jax.lax.fori_loop(0, n // unroll, trip, carry)


def _column(tile, pick):
    """The ``[N, 1]`` column of an ``[N, CHUNK]`` tile that ``pick`` (one
    lane of each row) marks."""
    return jnp.sum(jnp.where(pick, tile, 0.0), axis=1, keepdims=True)


def _forward_kernel(x_ref, d_ref, a_ref, bt_ref, ct_ref, dd_ref,
                    y_ref, s0_ref, s_scr):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    s0_ref[...] = s_scr[...]
    A, bt, ct, Dv = a_ref[...], bt_ref[...], ct_ref[...], dd_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)

    def step(t, s):
        pick = lane == t
        d_t = d_ref[pl.ds(t, 1), :]  # [1, channels]
        x_t = x_ref[pl.ds(t, 1), :]
        s = jnp.exp(d_t * A) * s + _column(bt, pick) * (d_t * x_t)
        y_ref[pl.ds(t, 1), :] = jnp.sum(
            s * _column(ct, pick), axis=0, keepdims=True) + Dv * x_t
        return s

    s_scr[...] = _loop(x_ref.shape[0], step, s_scr[...], UNROLL)


def _backward_kernel(x_ref, d_ref, a_ref, bt_ref, ct_ref, dd_ref, s0_ref,
                     dy_ref, dx_ref, ddelta_ref, dbt_ref, dct_ref, da_ref,
                     dD_ref, h_scr, prev_scr):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)
        da_ref[...] = jnp.zeros_like(da_ref)
        dD_ref[...] = jnp.zeros_like(dD_ref)

    A, bt, ct, Dv = a_ref[...], bt_ref[...], ct_ref[...], dd_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)
    L = x_ref.shape[0]

    def forward(t, s):  # the chunk's states again, each before its step
        prev_scr[t] = s
        d_t = d_ref[pl.ds(t, 1), :]
        return jnp.exp(d_t * A) * s + _column(bt, lane == t) * (
            d_t * x_ref[pl.ds(t, 1), :])

    _loop(L, forward, s0_ref[...], UNROLL)

    def back(i, carry):
        h, dA, dD, db_acc, dc_acc = carry
        t = L - 1 - i
        pick = lane == t
        s_prev = prev_scr[t]
        d_t = d_ref[pl.ds(t, 1), :]
        x_t = x_ref[pl.ds(t, 1), :]
        dy_t = dy_ref[pl.ds(t, 1), :]
        b, c = _column(bt, pick), _column(ct, pick)
        a = jnp.exp(d_t * A)
        u = d_t * x_t
        s = a * s_prev + b * u
        g = h + c * dy_t
        db_acc = jnp.where(pick, jnp.sum(g * u, axis=1, keepdims=True),
                           db_acc)
        dc_acc = jnp.where(pick, jnp.sum(s * dy_t, axis=1, keepdims=True),
                           dc_acc)
        du = jnp.sum(g * b, axis=0, keepdims=True)
        d_log = g * s_prev * a
        ddelta_ref[pl.ds(t, 1), :] = jnp.sum(
            d_log * A, axis=0, keepdims=True) + du * x_t
        dx_ref[pl.ds(t, 1), :] = du * d_t + Dv * dy_t
        return (a * g, dA + d_log * d_t, dD + x_t * dy_t, db_acc, dc_acc)

    zeros = jnp.zeros_like(bt)
    h, dA, dD, db_acc, dc_acc = _loop(
        L, back, (h_scr[...], jnp.zeros_like(A), jnp.zeros_like(Dv), zeros,
                  zeros), UNROLL)
    h_scr[...] = h
    da_ref[...] += dA
    dD_ref[...] += dD
    dbt_ref[...] = db_acc
    dct_ref[...] = dc_acc


def _vmem_limit(blocks: int, scratch: int) -> int:
    """VMEM for the blocks (double-buffered) and the scratch, and 8 MiB
    for the compiler's own."""
    return min(2 * blocks + scratch + (8 << 20), 100 << 20)


def _kernel_layout(x, A):
    batch, T, E = x.shape
    N = A.shape[1]
    w = channel_block(E)
    return batch, T, E, N, w, T // CHUNK, E // w


def kernel_forward(x, delta, A, B, C, D, interpret: bool = False):
    """:func:`xla_forward` as a Pallas kernel; shapes must satisfy
    :func:`kernel_applies`. ``interpret`` runs it in Pallas's interpreter
    (the CPU tests)."""
    batch, T, E, N, w, chunks, groups = _kernel_layout(x, A)
    f32 = jnp.float32
    rows = pl.BlockSpec((None, CHUNK, w), lambda b, g, k: (b, k, g))
    state = pl.BlockSpec((N, w), lambda b, g, k: (0, g))
    columns = pl.BlockSpec((None, N, CHUNK), lambda b, g, k: (b, 0, k))
    per_channel = pl.BlockSpec((1, w), lambda b, g, k: (0, g))
    row_bytes = CHUNK * w * 4
    y, states = pl.pallas_call(
        _forward_kernel,
        grid=(batch, groups, chunks),
        in_specs=[rows, rows, state, columns, columns, per_channel],
        out_specs=[rows, pl.BlockSpec((None, None, N, w),
                                      lambda b, g, k: (b, k, 0, g))],
        out_shape=[jax.ShapeDtypeStruct((batch, T, E), f32),
                   jax.ShapeDtypeStruct((batch, chunks, N, E), f32)],
        scratch_shapes=[pltpu.VMEM((N, w), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                3 * row_bytes + 4 * N * (w + CHUNK) * 4, N * w * 4)),
        interpret=interpret,
        name=FORWARD_NAME,
    )(x.astype(f32), delta.astype(f32), A.T.astype(f32),
      jnp.swapaxes(B, 1, 2).astype(f32), jnp.swapaxes(C, 1, 2).astype(f32),
      D.astype(f32).reshape(1, E))
    return y, states


def kernel_backward(x, delta, A, B, C, D, states, dy,
                    interpret: bool = False):
    """:func:`xla_backward` as a Pallas kernel, the chunks in reverse."""
    batch, T, E, N, w, chunks, groups = _kernel_layout(x, A)
    f32 = jnp.float32
    last = chunks - 1
    rows = pl.BlockSpec((None, CHUNK, w), lambda b, g, k: (b, last - k, g))
    state = pl.BlockSpec((N, w), lambda b, g, k: (0, g))
    columns = pl.BlockSpec((None, N, CHUNK),
                           lambda b, g, k: (b, 0, last - k))
    per_channel = pl.BlockSpec((1, w), lambda b, g, k: (0, g))
    saved = pl.BlockSpec((None, None, N, w),
                         lambda b, g, k: (b, last - k, 0, g))
    partial_columns = pl.BlockSpec((None, None, N, CHUNK),
                                   lambda b, g, k: (b, g, 0, last - k))
    row_bytes = CHUNK * w * 4
    dx, d_delta, dBt, dCt, dA, dD = pl.pallas_call(
        _backward_kernel,
        grid=(batch, groups, chunks),
        in_specs=[rows, rows, state, columns, columns, per_channel, saved,
                  rows],
        out_specs=[rows, rows, partial_columns, partial_columns,
                   pl.BlockSpec((None, N, w), lambda b, g, k: (b, 0, g)),
                   pl.BlockSpec((None, 1, w), lambda b, g, k: (b, 0, g))],
        out_shape=[jax.ShapeDtypeStruct((batch, T, E), f32),
                   jax.ShapeDtypeStruct((batch, T, E), f32),
                   jax.ShapeDtypeStruct((batch, groups, N, T), f32),
                   jax.ShapeDtypeStruct((batch, groups, N, T), f32),
                   jax.ShapeDtypeStruct((batch, N, E), f32),
                   jax.ShapeDtypeStruct((batch, 1, E), f32)],
        scratch_shapes=[pltpu.VMEM((N, w), f32),
                        pltpu.VMEM((CHUNK, N, w), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                5 * row_bytes + 4 * N * (2 * w + 2 * CHUNK) * 4,
                (CHUNK + 1) * N * w * 4)),
        interpret=interpret,
        name=BACKWARD_NAME,
    )(x.astype(f32), delta.astype(f32), A.T.astype(f32),
      jnp.swapaxes(B, 1, 2).astype(f32), jnp.swapaxes(C, 1, 2).astype(f32),
      D.astype(f32).reshape(1, E), states, dy.astype(f32))
    return (dx, d_delta, jnp.sum(dA, axis=0).T,
            jnp.swapaxes(jnp.sum(dBt, axis=1), 1, 2),
            jnp.swapaxes(jnp.sum(dCt, axis=1), 1, 2),
            jnp.sum(dD, axis=(0, 1)))


# --- the scan with its gradient -------------------------------------------------


def _with_rules(forward, backward):
    """The scan as a ``jax.custom_vjp`` of its forward and backward forms;
    the residuals are the inputs and the saved chunk states."""

    @jax.custom_vjp
    def scan(x, delta, A, B, C, D):
        return forward(x, delta, A, B, C, D)[0]

    def scan_forward(x, delta, A, B, C, D):
        y, states = forward(x, delta, A, B, C, D)
        return y, (x, delta, A, B, C, D, states)

    def scan_backward(residuals, dy):
        x, delta, A, B, C, D, states = residuals
        grads = backward(x, delta, A, B, C, D, states, dy)
        return tuple(g.astype(r.dtype) for g, r in zip(grads, residuals))

    scan.defvjp(scan_forward, scan_backward)
    return scan


xla_scan = _with_rules(xla_forward, xla_backward)


def _by_platform(kernel, xla):
    return lambda *args: jax.lax.platform_dependent(
        *args, default=xla, tpu=kernel)


_platform_scan = _with_rules(_by_platform(kernel_forward, xla_forward),
                             _by_platform(kernel_backward, xla_backward))


def selective_scan(x, delta, A, B, C, D):
    """``y [batch, T, E]`` f32 of the recurrence in this module's
    docstring, with gradients for all six inputs, in the form the code
    can observe to be the right one: shapes the kernels do not take
    (:func:`kernel_applies`), the XLA form; else the kernels where the
    program is LOWERED for a TPU (a compile for a described chip under
    ``JAX_PLATFORMS=cpu`` included) and the XLA form on any other
    platform."""
    if not kernel_applies(x.shape[1], x.shape[2]):
        return xla_scan(x, delta, A, B, C, D)
    return _platform_scan(x, delta, A, B, C, D)

