"""ResNet-18 with GroupNorm, NHWC, for 32x32 inputs.

Flagship model for the scale config "non-IID Dirichlet(0.1), 1000 clients,
ResNet-18" (BASELINE.json configs[4]). Deliberate TPU/FL design choice:
GroupNorm instead of BatchNorm — BatchNorm's running statistics are mutable
non-parameter state that (a) breaks the pure client-stacked-params discipline
under ``vmap`` and (b) is known to degrade under federated averaging of
per-client statistics; GroupNorm keeps the model a pure function of params.
Convs run in bfloat16 on the MXU; logits returned float32.

W-folded stage 1 (federated-vmap TPU layout): 64-channel tensors tile
(8, 128) with the lane dim padded 64 -> 128 — 2x HBM inflation on exactly
the stage that dominates the per-client-weights round (flagship profile:
64-ch ops moved 423 GiB at ~278 GB/s vs ~660 GB/s for 128+-ch ops).
Folding W-pairs into channels — ``[B, H, W, 64] -> [B, H, W/2, 128]``, a
PURE reshape of the trailing dims — fills the lanes. A stride-1 3x3 conv
on the folded form is a 3x3 conv with a packed kernel built from the
ordinary ``[3, 3, cin, cout]`` parameter by six static slice-assignments
(:func:`pack_folded_kernel`; 50% fill -> 2x MXU FLOPs, paid from idle MXU
capacity since the op is bandwidth-bound). The math is exact (the packing
transpose discards zero-slot gradients), parameters are identical to the
unfolded model, and GroupNorm statistics are computed on the unfolded
VIEW (a fused reshape). Measured fwd+bwd per conv at chunk 40 x batch 25:
88 -> 10.6 ms isolated (scripts/exp_folded_conv.py); whole-round effect in
docs/PERFORMANCE.md.

Round-5 negative results (kept so nobody re-tries them): (1) re-orienting
the folded stage HWNC (batch second-minor, so the standard layout matches
the conv backend's preferred {3,0,2,1}) measured 3.7x faster on an
ISOLATED stage-1 block chain (scripts/exp_stage1_layout.py) but made the
real sign_SGD round 7% SLOWER (2.72 -> 2.91 s) while leaving the bf16
fed/fed_quant rounds flat — in context the round's other consumers
re-introduce relayouts elsewhere. (2) `lax.optimization_barrier` between
conv outputs and the GroupNorm f32 convert (to stop XLA writing conv
outputs f32 via `convolution_convert_fusion` epilogues and re-reading
them at 2x bytes in the wgrad fusions) costs more fusion than it saves:
2.72 -> 3.17 s. Only in-context measurement is valid evidence here (the
round-3 tap-einsum lesson, re-learned twice).
"""

from __future__ import annotations

import functools
import os
from typing import Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_learning_simulator_tpu.ops.gn_pallas import pallas_group_norm


# Read ONCE at import (ADVICE r5): the flag selects which GroupNorm forward
# gets COMPILED into the round program, so flipping the env var after the
# first compile could not take effect anyway — the jit cache would keep
# serving the stale path silently. A module constant makes the
# first-read-wins semantics explicit; in-process tests that genuinely need
# both kernels toggle the constant itself (test_folded_resnet.py).
_GN_PALLAS_ENABLED = os.environ.get("DLS_GN_PALLAS", "0") == "1"


def _use_pallas_gn() -> bool:
    """Opt-in Pallas GroupNorm forward (``DLS_GN_PALLAS=1``, TPU only).

    MEASURED NEGATIVE RESULT (round 5): the kernels (ops/gn_pallas.py)
    do exactly what the trace analysis asked for — the conv emits bf16
    (a Pallas call is an opaque consumer, so XLA cannot fuse the stats'
    f32 convert into the conv epilogue), stats read the activations once
    with in-register converts, normalize reads them once more — and the
    REAL rounds got slower anyway: sign_SGD 2.72 -> 3.37 s/round, fed
    flagship 2.22 -> 2.84. The f32-activation "tax" the jnp path pays is
    XLA's price for fusing normalize/relu/residual/wgrad-recompute into
    neighboring ops, and that fusion is worth more than the saved
    bytes. Third structural attack on the stage-1 f32 sharing (after
    HWNC orientation and optimization_barrier, module docstring), third
    in-context rejection — the jnp path stands as the measured floor."""
    if not _GN_PALLAS_ENABLED:
        return False
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            "DLS_GN_PALLAS=1 selects the Mosaic GroupNorm kernels, which "
            f"exist only on TPU; the default backend is {backend!r}"
        )
    return True


def pack_folded_kernel(w):
    """``[3, 3, cin, cout] -> [3, 3, 2cin, 2cout]`` for the W-folded conv.

    Output fold position ``sx`` and input fold position ``tx``: an original
    tap ``dx`` at output column ``2J+sx`` reads input column
    ``2J + (sx+dx-1) = 2(J+V) + tx`` — six (sx, dx) placements, zeros
    elsewhere. Exact; autodiff's transpose scatters gradients back to the
    six slots and discards the zero slots.
    """
    cin, cout = w.shape[2], w.shape[3]
    zero = jnp.zeros((3, cin, cout), w.dtype)

    # Trailing-dim block assembly ONLY (concat over the ci/co axes, stack
    # over the leading tap axis): an .at[].set build lowers to ~20 GB/s
    # dynamic-update-slice chains, and a stack+6D-transpose materializes
    # the full packed tensor twice — both measured as real round costs.
    def tap(v, tx, sx):
        dx = 2 * v + tx - sx + 1
        return w[:, dx] if 0 <= dx <= 2 else zero

    vs = []
    for v in (-1, 0, 1):
        rows = [
            jnp.concatenate([tap(v, tx, 0), tap(v, tx, 1)], axis=-1)
            for tx in range(2)
        ]
        vs.append(jnp.concatenate(rows, axis=-2))  # [3(dy), 2cin, 2cout]
    return jnp.stack(vs, axis=1)  # [3(dy), 3(v), 2cin, 2cout]


def pack_folded_stride2_kernel(w):
    """``[3, 3, cin, cout] -> [3, 2, 2cin, cout]``: stride-2 3x3 conv
    consuming the folded layout, producing the UNFOLDED downsampled map.

    SAME padding at stride 2 pads (low 0, high 1), so unfolded output
    column j reads input columns ``2j+dx = 2(j+V)+tx``, V in {0, 1}: a
    (3, 2)-tap conv on folded cols with strides (2, 1) and explicit
    padding ((0, 1), (0, 1)). 3 of 4 (V, tx) slots are live.
    """
    cin, cout = w.shape[2], w.shape[3]
    zero = jnp.zeros((3, cin, cout), w.dtype)

    def tap(v, tx):
        dx = 2 * v + tx
        return w[:, dx] if 0 <= dx <= 2 else zero

    vs = [
        jnp.concatenate([tap(v, 0), tap(v, 1)], axis=-2)  # [3, 2cin, cout]
        for v in (0, 1)
    ]
    return jnp.stack(vs, axis=1)  # [3(dy), 2(v), 2cin, cout]


def pack_folded_pointwise_stride2(w):
    """``[1, 1, cin, cout] -> [1, 1, 2cin, cout]``: the 1x1 stride-2
    projection reads only even columns = the tx=0 half of a folded pixel."""
    return jnp.concatenate([w, jnp.zeros_like(w)], axis=2)


def pack_folded_stem_kernel(w):
    """``[3, 3, cin, cout] -> [3, 4, cin, 2cout]``: stride-1 SAME 3x3 conv
    on the UNFOLDED input emitting the FOLDED layout directly.

    Folded output pixel (J, tx in {0, 1}) holds unfolded column 2J+tx in
    channel block tx*cout; tap dx reads input column 2J + (tx+dx-1) =
    2J + (k-1) with k = tx+dx in {0..3} — a (3, 4)-tap conv at column
    stride 2 with explicit (1, 1) column padding. Six live placements in
    twelve slots; with it, no unfolded stage-1 activation ever
    materializes (the fold 'reshape' at the stem boundary is physically a
    relayout copy, and its f32 GroupNorm-backward intermediates were
    measured at 348-420 GB/s on lane-padded [.., W, 64] tensors —
    docs/PERFORMANCE.md round 4)."""
    zero = jnp.zeros(w.shape[:1] + w.shape[2:], w.dtype)  # [3, cin, cout]

    def tap(k, tx):
        dx = k - tx
        return w[:, dx] if 0 <= dx <= 2 else zero

    ks = [
        jnp.concatenate([tap(k, 0), tap(k, 1)], axis=-1)  # [3, cin, 2cout]
        for k in range(4)
    ]
    return jnp.stack(ks, axis=1)  # [3(ky), 4(k), cin, 2cout]


class FoldedStemConv(nn.Module):
    """CIFAR stem conv producing the W-folded stage-1 layout directly.

    The parameter is the ordinary unfolded ``[3, 3, cin, features]`` kernel
    under the same auto-name/shape/init as the ``nn.Conv`` stem it replaces
    (instantiate with ``name="Conv_0"`` for checkpoint-identical trees)."""

    features: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (3, 3, x.shape[-1], self.features), jnp.float32,
        )
        wp = pack_folded_stem_kernel(kernel.astype(self.dtype))
        return jax.lax.conv_general_dilated(
            x.astype(self.dtype), wp, (1, 2), ((1, 1), (1, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )


class FoldedConv3x3(nn.Module):
    """Stride-1 SAME 3x3 conv on the W-folded layout ``[B, H, W/2, 2cin]``.

    The parameter is the ordinary unfolded ``[3, 3, cin, cout]`` kernel
    (same name/shape/init as ``nn.Conv``); packing happens per forward.
    """

    features: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, xf):
        cin = xf.shape[-1] // 2
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (3, 3, cin, self.features), jnp.float32,
        )
        wp = pack_folded_kernel(kernel.astype(self.dtype))
        return jax.lax.conv_general_dilated(
            xf.astype(self.dtype), wp, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )


def _fgn_forward(xf, scale, bias, g: int, eps: float, out_dtype):
    """Folded-layout GroupNorm forward; returns (y, mean, rstd).

    Coefficient form (round 5): the normalize is ``y = x*a + b`` with
    per-(sample, tx, group) f32 coefficients folded from
    (mean, rstd, scale, bias) — so the only big-tensor consumers are ONE
    inline-convert stats reduce and ONE bf16-in/bf16-out elementwise
    pass. The earlier ``((x - mean) * rstd) * scale + bias`` form made
    XLA materialize a relayouted f32 copy of every stage-1 GN input
    (resnet.py:175 in the r4 HLO): the copy itself cost ~0.6 ms/use and
    the conv weight-grad fusions then re-read activations at f32 (2x)
    bytes — together ~20% of the sign_SGD round (measured, HLO-verified:
    the copies' consumers were the transpose(jvp) conv wgrad fusions).
    """
    b, h, wf, c2 = xf.shape
    c = c2 // 2
    cpg = c // g
    if _use_pallas_gn():
        y, mean_g, rstd_g = pallas_group_norm(
            xf, jnp.tile(scale, 2), jnp.tile(bias, 2), g, eps, out_dtype,
            folds=2,
        )
        return (
            y,
            mean_g.reshape(b, 1, 1, 1, g, 1),
            rstd_g.reshape(b, 1, 1, 1, g, 1),
        )
    x6 = xf.reshape(b, h, wf, 2, g, cpg)
    x32 = x6.astype(jnp.float32)
    # One-pass statistics (E[x^2] - E[x]^2, flax's use_fast_variance):
    # the two-pass (x - mean)^2 form reads the activations twice and
    # measurably halves this fusion's effective bandwidth. (An
    # indicator-matrix matmul formulation of the group reduction was
    # also tried — identical round time, so the simpler form stays.)
    mean = jnp.mean(x32, axis=(1, 2, 3, 5), keepdims=True)
    mean2 = jnp.mean(jnp.square(x32), axis=(1, 2, 3, 5), keepdims=True)
    var = jnp.maximum(mean2 - jnp.square(mean), 0.0)
    rstd = jax.lax.rsqrt(var + eps)
    scale2 = jnp.tile(scale, 2).reshape(2, g, cpg)
    bias2 = jnp.tile(bias, 2).reshape(2, g, cpg)
    a = rstd * scale2          # [b, 1, 1, 2, g, cpg] — b x 2c floats
    # Subtract-first, then one multiply: folding mean into the additive
    # coefficient (y = x*a + (bias - mean*a)) cancels catastrophically
    # when |x - mean| << |x| (measured: 1% stem-wgrad error at f32).
    y = ((x6 - mean) * a + bias2).astype(out_dtype).reshape(b, h, wf, c2)
    return y, mean, rstd


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _folded_group_norm(xf, scale, bias, g: int, eps: float, out_dtype):
    return _fgn_forward(xf, scale, bias, g, eps, out_dtype)[0]


def _fgn_fwd(xf, scale, bias, g, eps, out_dtype):
    y, mean, rstd = _fgn_forward(xf, scale, bias, g, eps, out_dtype)
    # bias rides along only for its dtype (cotangents must match primal
    # dtypes); it is a [C] vector, so the residual cost is nil.
    return y, (xf, scale, bias, mean, rstd)


def _fgn_bwd(g: int, eps: float, out_dtype, res, dy):
    """Canonical closed-form GN backward, two activation passes.

    XLA autodiff of the E[x^2]-E[x]^2 forward emits a chain of separate
    stat reduces over the stage-1 activations (measured 243 GB/s,
    ~211 ms/round on the flagship — docs/PERFORMANCE.md round 4); the
    closed form needs one fused reduce pass (m1, m2, dscale, dbias share
    the same two inputs) and one elementwise pass for dx:

      dx = rstd * (dy*scale - mean_grp(dy*scale)
                   - xhat * mean_grp(dy*scale * xhat))
    """
    xf, scale, bias, mean, rstd = res
    b, h, wf, c2 = xf.shape
    c = c2 // 2
    cpg = c // g
    x6 = xf.reshape(b, h, wf, 2, g, cpg)
    dy6 = dy.reshape(b, h, wf, 2, g, cpg)
    x32 = x6.astype(jnp.float32)
    dy32 = dy6.astype(jnp.float32)
    scale2 = jnp.tile(scale, 2).reshape(2, g, cpg)
    xhat = (x32 - mean) * rstd
    dyg = dy32 * scale2
    m1 = jnp.mean(dyg, axis=(1, 2, 3, 5), keepdims=True)
    m2 = jnp.mean(dyg * xhat, axis=(1, 2, 3, 5), keepdims=True)
    # The dx pass re-reads dy6/x6 directly (xhat recomputed in-register
    # from the bf16 x6) with per-(sample, group) f32 coefficients — no
    # materialized f32 xhat/dyg shared with the reduces (see
    # _fgn_forward's rationale). Same subtract-first numerics as the old
    # form; only the read dtype of the big tensors changed.
    dx = ((dyg - m1 - xhat * m2) * rstd).astype(xf.dtype)
    dx = dx.reshape(b, h, wf, c2)
    # Per-channel param grads: both tx placements of channel c accumulate
    # (sum over the tx axis of the [g, cpg] reduce). Cotangent dtypes must
    # match the incoming params' dtypes (bf16 when the engine runs
    # local_compute_dtype=bfloat16).
    dscale = jnp.sum(dy32 * xhat, axis=(0, 1, 2, 3))
    dscale = dscale.reshape(c).astype(scale.dtype)
    dbias = jnp.sum(dy32, axis=(0, 1, 2, 3)).reshape(c).astype(bias.dtype)
    return dx, dscale, dbias


_folded_group_norm.defvjp(_fgn_fwd, _fgn_bwd)


def _gn_forward(x, scale, bias, g: int, eps: float, out_dtype):
    """Unfolded NHWC GroupNorm forward; returns (y, mean, rstd).

    Same coefficient form as :func:`_fgn_forward` (y = x*a + b with small
    per-(sample, group) f32 coefficients): the activations are read in
    their stored dtype by exactly one reduce and one elementwise pass, so
    no relayouted f32 activation copy materializes for the conv
    weight-grad recompute to re-read at 2x bytes."""
    b, h, w, c = x.shape
    cpg = c // g
    if _use_pallas_gn():
        y, mean_g, rstd_g = pallas_group_norm(
            x, scale, bias, g, eps, out_dtype, folds=1,
        )
        return (
            y,
            mean_g.reshape(b, 1, 1, g, 1),
            rstd_g.reshape(b, 1, 1, g, 1),
        )
    x5 = x.reshape(b, h, w, g, cpg)
    x32 = x5.astype(jnp.float32)
    mean = jnp.mean(x32, axis=(1, 2, 4), keepdims=True)
    mean2 = jnp.mean(jnp.square(x32), axis=(1, 2, 4), keepdims=True)
    var = jnp.maximum(mean2 - jnp.square(mean), 0.0)
    rstd = jax.lax.rsqrt(var + eps)
    scale5 = scale.reshape(g, cpg)
    a = rstd * scale5
    # Subtract-first (same rationale as _fgn_forward): folding mean into
    # the additive coefficient cancels catastrophically when
    # |x - mean| << |x|.
    y = ((x5 - mean) * a + bias.reshape(g, cpg)).astype(out_dtype)
    y = y.reshape(b, h, w, c)
    return y, mean, rstd


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _plain_group_norm(x, scale, bias, g: int, eps: float, out_dtype):
    return _gn_forward(x, scale, bias, g, eps, out_dtype)[0]


def _pgn_fwd(x, scale, bias, g, eps, out_dtype):
    y, mean, rstd = _gn_forward(x, scale, bias, g, eps, out_dtype)
    return y, (x, scale, bias, mean, rstd)


def _pgn_bwd(g: int, eps: float, out_dtype, res, dy):
    """Closed-form GN backward for the unfolded layout (same derivation
    as :func:`_fgn_bwd`, without the tx fold)."""
    x, scale, bias, mean, rstd = res
    b, h, w, c = x.shape
    cpg = c // g
    x5 = x.reshape(b, h, w, g, cpg)
    dy5 = dy.reshape(b, h, w, g, cpg)
    x32 = x5.astype(jnp.float32)
    dy32 = dy5.astype(jnp.float32)
    scale5 = scale.reshape(g, cpg)
    xhat = (x32 - mean) * rstd
    dyg = dy32 * scale5
    m1 = jnp.mean(dyg, axis=(1, 2, 4), keepdims=True)
    m2 = jnp.mean(dyg * xhat, axis=(1, 2, 4), keepdims=True)
    # Same subtract-first numerics as _fgn_bwd: bf16 reads, f32 register
    # math, xhat recomputed in-register rather than folding mean into an
    # additive coefficient (cancellation — see _fgn_forward).
    dx = ((dyg - m1 - xhat * m2) * rstd).astype(x.dtype).reshape(b, h, w, c)
    dscale = jnp.sum(dy32 * xhat, axis=(0, 1, 2)).reshape(c).astype(scale.dtype)
    dbias = jnp.sum(dy32, axis=(0, 1, 2)).reshape(c).astype(bias.dtype)
    return dx, dscale, dbias


_plain_group_norm.defvjp(_pgn_fwd, _pgn_bwd)


class PlainGroupNorm(nn.Module):
    """GroupNorm with the closed-form backward (:func:`_pgn_bwd`).

    Replaces ``nn.GroupNorm`` in the unfolded blocks — same parameter
    names/shapes/init (instantiate with ``name="GroupNorm_N"`` to keep
    flax auto-named trees identical), same one-pass E[x^2]-E[x]^2
    statistics. Numerics: f32-exact against flax; under bf16 the affine
    is applied in f32 and cast ONCE at the output (flax casts operands to
    bf16 first), so bf16 outputs agree within an output ulp rather than
    bitwise — tests/test_folded_resnet.py covers both. Exists because XLA
    autodiff of the statistics emits separate VPU-bound stat-reduce
    passes per GroupNorm (docs/PERFORMANCE.md round 4);
    ``custom_backward=False`` restores autodiff of the same forward.
    """

    num_groups: int
    dtype: jnp.dtype = jnp.bfloat16
    epsilon: float = 1e-6
    custom_backward: bool = True

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        if c % self.num_groups:
            # nn.GroupNorm raises this clearly at call time; keep the
            # clear error rather than a reshape failure inside jit.
            raise ValueError(
                f"number of groups ({self.num_groups}) must divide the "
                f"channel count ({c})"
            )
        scale = self.param("scale", nn.initializers.ones, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        if self.custom_backward:
            return _plain_group_norm(
                x, scale, bias, self.num_groups, self.epsilon, self.dtype
            )
        y, _, _ = _gn_forward(
            x, scale, bias, self.num_groups, self.epsilon, self.dtype
        )
        return y


class FoldedGroupNorm(nn.Module):
    """GroupNorm computed directly ON the folded layout.

    GroupNorm over folded channels naively would pool the two folded
    columns' channel ranges into wrong groups. Unfolding for an inner
    ``nn.GroupNorm`` is correct but breaks XLA fusion at the reshape
    boundary (measured: the stats re-read the activations as separate
    ~380 GB/s reduces, ~0.5 s/round). Instead: folded channel
    ``c' = tx*C + g*cpg + i``, so a trailing-dim reshape to
    ``[.., 2(tx), G, cpg]`` exposes the group axis and the statistics
    reduce over ``(H, Wf, tx, cpg)`` — same elements as the unfolded
    norm, never leaving the folded layout. scale/bias are per-channel
    ``[C]`` (identical to ``nn.GroupNorm``'s params), tiled across tx.
    The backward is the hand-written closed form (:func:`_fgn_bwd`);
    ``custom_backward=False`` restores plain autodiff.
    """

    num_groups: int
    dtype: jnp.dtype = jnp.bfloat16
    epsilon: float = 1e-6
    custom_backward: bool = True

    @nn.compact
    def __call__(self, xf):
        c = xf.shape[-1] // 2
        if c % self.num_groups:
            raise ValueError(
                f"number of groups ({self.num_groups}) must divide the "
                f"channel count ({c})"
            )
        scale = self.param("scale", nn.initializers.ones, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        if self.custom_backward:
            return _folded_group_norm(
                xf, scale, bias, self.num_groups, self.epsilon, self.dtype
            )
        y, _, _ = _fgn_forward(
            xf, scale, bias, self.num_groups, self.epsilon, self.dtype
        )
        return y


class FoldedResidualBlock(nn.Module):
    """Stage-1 basic block on the W-folded layout (stride 1, no
    projection — exactly the shape regime where folding applies)."""

    features: int
    dtype: jnp.dtype = jnp.bfloat16
    gn_custom_backward: bool = True

    @nn.compact
    def __call__(self, xf):
        residual = xf
        y = FoldedConv3x3(self.features, dtype=self.dtype)(xf)
        y = FoldedGroupNorm(
            num_groups=min(32, self.features), dtype=self.dtype,
            custom_backward=self.gn_custom_backward,
        )(y)
        y = nn.relu(y)
        y = FoldedConv3x3(self.features, dtype=self.dtype)(y)
        y = FoldedGroupNorm(
            num_groups=min(32, self.features), dtype=self.dtype,
            custom_backward=self.gn_custom_backward,
        )(y)
        return nn.relu(y + residual)


class FoldedTransitionBlock(nn.Module):
    """Stage-2 entry block (stride-2, with projection shortcut) consuming
    the FOLDED stage-1 output directly: the stride-2 convs read folded
    (lane-full) inputs and produce the unfolded downsampled map, so the
    explicit unfold reshape — and the padded stride-2 convs on
    ``[.., 32, 32, 64]`` it fed — disappear entirely."""

    features: int
    dtype: jnp.dtype = jnp.bfloat16
    gn_custom_backward: bool = True

    @nn.compact
    def __call__(self, xf):
        cin = xf.shape[-1] // 2
        w1 = self.param(
            "conv1_kernel", nn.initializers.lecun_normal(),
            (3, 3, cin, self.features), jnp.float32,
        )
        y = jax.lax.conv_general_dilated(
            xf.astype(self.dtype),
            pack_folded_stride2_kernel(w1.astype(self.dtype)),
            (2, 1), ((0, 1), (0, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        y = PlainGroupNorm(
            num_groups=min(32, self.features), dtype=self.dtype,
            name="GroupNorm_0",
            custom_backward=self.gn_custom_backward,
        )(y)
        y = nn.relu(y)
        y = nn.Conv(self.features, (3, 3), padding="SAME", use_bias=False,
                    dtype=self.dtype)(y)
        y = PlainGroupNorm(
            num_groups=min(32, self.features), dtype=self.dtype,
            name="GroupNorm_1",
            custom_backward=self.gn_custom_backward,
        )(y)
        wp = self.param(
            "proj_kernel", nn.initializers.lecun_normal(),
            (1, 1, cin, self.features), jnp.float32,
        )
        residual = jax.lax.conv_general_dilated(
            xf.astype(self.dtype),
            pack_folded_pointwise_stride2(wp.astype(self.dtype)),
            (2, 1), ((0, 0), (0, 0)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        residual = PlainGroupNorm(
            num_groups=min(32, self.features), dtype=self.dtype,
            name="GroupNorm_2",
            custom_backward=self.gn_custom_backward,
        )(residual)
        return nn.relu(y + residual)


class ResidualBlock(nn.Module):
    features: int
    strides: int = 1
    dtype: jnp.dtype = jnp.bfloat16
    gn_custom_backward: bool = True

    @nn.compact
    def __call__(self, x):
        residual = x
        y = nn.Conv(
            self.features, (3, 3), strides=(self.strides, self.strides),
            padding="SAME", use_bias=False, dtype=self.dtype,
        )(x)
        y = PlainGroupNorm(num_groups=min(32, self.features),
                           dtype=self.dtype, name="GroupNorm_0",
                           custom_backward=self.gn_custom_backward)(y)
        y = nn.relu(y)
        y = nn.Conv(
            self.features, (3, 3), padding="SAME", use_bias=False, dtype=self.dtype
        )(y)
        y = PlainGroupNorm(num_groups=min(32, self.features),
                           dtype=self.dtype, name="GroupNorm_1",
                           custom_backward=self.gn_custom_backward)(y)
        if residual.shape != y.shape:
            residual = nn.Conv(
                self.features, (1, 1), strides=(self.strides, self.strides),
                use_bias=False, dtype=self.dtype,
            )(residual)
            residual = PlainGroupNorm(
                num_groups=min(32, self.features), dtype=self.dtype,
                name="GroupNorm_2",
                custom_backward=self.gn_custom_backward,
            )(residual)
        return nn.relu(y + residual)


class ResNet18(nn.Module):
    """Generic basic-block ResNet; default stage sizes give ResNet-18."""

    num_classes: int = 10
    stage_sizes: Sequence[int] = (2, 2, 2, 2)
    width: int = 64
    dtype: jnp.dtype = jnp.bfloat16
    # W-folded stage 1 (module docstring): lane-filling layout for the
    # 64-channel stage. Identical parameters and math; only the compute
    # layout changes. Applicable when the stage is stride-1 at width 64
    # with an even spatial W — the CIFAR-style configuration.
    fold_stage1: bool = True
    # Closed-form GroupNorm backward (custom_vjp) throughout; False
    # restores XLA autodiff of the same forward. Escape hatch reachable
    # via --model_args '{"gn_custom_backward": false}'.
    gn_custom_backward: bool = True

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype)
        # Fold applicability: stage 0 is stride-1 at width 64 with even
        # spatial dims (even W: the fold pairs columns; even H: the
        # transition block's stride-2 row taps assume SAME's (0, 1)
        # padding). The stem preserves spatial dims, so the input decides.
        fold_ok = (
            self.fold_stage1
            and self.width == 64
            and x.shape[1] % 2 == 0
            and x.shape[2] % 2 == 0
        )
        # CIFAR-style stem (3x3, no initial downsample) for 32x32 inputs.
        # When folding, the stem itself emits the folded layout (name= pins
        # keep the parameter tree identical to the unfolded stem's): no
        # unfolded 64-channel activation — nor its lane-padded
        # GroupNorm-backward intermediates — ever materializes.
        folded = False
        if fold_ok:
            x = FoldedStemConv(
                self.width, dtype=self.dtype, name="Conv_0"
            )(x)
            x = FoldedGroupNorm(
                num_groups=min(32, self.width), dtype=self.dtype,
                name="GroupNorm_0",
                custom_backward=self.gn_custom_backward,
            )(x)
            x = nn.relu(x)
            folded = True
        else:
            x = nn.Conv(self.width, (3, 3), padding="SAME", use_bias=False,
                        dtype=self.dtype)(x)
            x = PlainGroupNorm(
                num_groups=min(32, self.width), dtype=self.dtype,
                name="GroupNorm_0",
                custom_backward=self.gn_custom_backward,
            )(x)
            x = nn.relu(x)
        for stage, n_blocks in enumerate(self.stage_sizes):
            features = self.width * (2**stage)
            if stage == 0 and folded:
                for block in range(n_blocks):
                    x = FoldedResidualBlock(
                        features, dtype=self.dtype,
                        gn_custom_backward=self.gn_custom_backward,
                    )(x)
                continue
            for block in range(n_blocks):
                strides = 2 if stage > 0 and block == 0 else 1
                if folded and block == 0:
                    # Stride-2 entry consumes the folded map directly and
                    # emits the unfolded downsampled one.
                    x = FoldedTransitionBlock(
                        features, dtype=self.dtype,
                        gn_custom_backward=self.gn_custom_backward,
                    )(x)
                    folded = False
                else:
                    x = ResidualBlock(
                        features, strides, dtype=self.dtype,
                        gn_custom_backward=self.gn_custom_backward,
                    )(x)
        if folded:  # single-stage configuration: unfold for the head
            b, h, wf, c2 = x.shape
            x = x.reshape(b, h, wf * 2, c2 // 2)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
        return x.astype(jnp.float32)


def ResNet34(num_classes: int = 10, **kwargs):
    """ResNet-34 stage configuration of the same basic-block network."""
    kwargs.setdefault("stage_sizes", (3, 4, 6, 3))
    return ResNet18(num_classes=num_classes, **kwargs)
