"""W-folded stage 1 of the ResNet (models/resnet.py): exact-math layout
transform, not an architecture change. The folded model must compute the
SAME function as the unfolded one given the same parameters."""

import jax
import jax.numpy as jnp
import numpy as np

from distributed_learning_simulator_tpu.models.resnet import (
    ResNet18,
    pack_folded_kernel,
)


def test_pack_folded_kernel_exact():
    """Folded conv == plain conv on the folded/unfolded views (f32)."""
    key = jax.random.key(0)
    x = jax.random.normal(key, (2, 8, 8, 4), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, 3, 4, 4),
                          jnp.float32)

    def conv(xx, ww):
        return jax.lax.conv_general_dilated(
            xx, ww, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )

    y_ref = conv(x, w)
    xf = x.reshape(2, 8, 4, 8)
    y_fold = conv(xf, pack_folded_kernel(w)).reshape(y_ref.shape)
    np.testing.assert_allclose(
        np.asarray(y_fold), np.asarray(y_ref), rtol=1e-5, atol=1e-5
    )


def _transplant(unfolded, folded):
    """Copy the unfolded model's params into the folded model's tree."""
    out = jax.tree_util.tree_map(lambda x: x, folded)  # deep-ish copy
    n_folded = len([k for k in folded if k.startswith("FoldedResidualBlock")])
    for i in range(n_folded):
        src = unfolded[f"ResidualBlock_{i}"]
        dst = out[f"FoldedResidualBlock_{i}"]
        for j in range(2):
            dst[f"FoldedConv3x3_{j}"]["kernel"] = src[f"Conv_{j}"]["kernel"]
            dst[f"FoldedGroupNorm_{j}"]["scale"] = src[f"GroupNorm_{j}"][
                "scale"
            ]
            dst[f"FoldedGroupNorm_{j}"]["bias"] = src[f"GroupNorm_{j}"][
                "bias"
            ]
    # Transition block (stage-2 entry): unfolded ResidualBlock_{n_folded}
    # with a projection shortcut (Conv_2/GroupNorm_2).
    trans = unfolded[f"ResidualBlock_{n_folded}"]
    ftb = out["FoldedTransitionBlock_0"]
    ftb["conv1_kernel"] = trans["Conv_0"]["kernel"]
    ftb["Conv_0"]["kernel"] = trans["Conv_1"]["kernel"]
    ftb["proj_kernel"] = trans["Conv_2"]["kernel"]
    for j in range(3):
        ftb[f"GroupNorm_{j}"] = trans[f"GroupNorm_{j}"]
    n_rest = len([k for k in folded if k.startswith("ResidualBlock")])
    for k in range(n_rest):
        out[f"ResidualBlock_{k}"] = unfolded[
            f"ResidualBlock_{k + n_folded + 1}"
        ]
    for shared in ("Conv_0", "GroupNorm_0", "Dense_0"):
        out[shared] = unfolded[shared]
    return out


def test_folded_resnet_matches_unfolded():
    """Same params -> same logits (f32 exact up to accumulation order;
    bf16 within a couple of output ulps)."""
    x = np.asarray(
        jax.random.normal(jax.random.key(2), (4, 32, 32, 3), jnp.float32)
    )
    for dtype, tol in ((jnp.float32, 1e-4), (jnp.bfloat16, 0.15)):
        unfolded_model = ResNet18(fold_stage1=False, dtype=dtype)
        folded_model = ResNet18(fold_stage1=True, dtype=dtype)
        pu = unfolded_model.init(jax.random.key(0), x[:1])["params"]
        pf = folded_model.init(jax.random.key(0), x[:1])["params"]
        pf = _transplant(pu, pf)
        yu = unfolded_model.apply({"params": pu}, x)
        yf = folded_model.apply({"params": pf}, x)
        np.testing.assert_allclose(
            np.asarray(yf), np.asarray(yu), rtol=tol, atol=tol,
        ), dtype


def test_folded_resnet_gradients_match_unfolded():
    """The packing transpose (autodiff of the concat/stack kernel build)
    must route gradients back to the SAME unpacked parameters: compare
    d loss / d params between folded and unfolded models in f32.
    Forward equality alone would not catch a scatter/duplication bug in
    the backward of pack_folded_kernel.

    Comparison metric: the two models compute the same math with
    different op orders (packed vs plain conv contractions, 6D vs 5D
    GroupNorm stat reduces), so forward activations differ by ~1 f32
    ulp — and a ulp-scale perturbation that lands exactly on a ReLU
    threshold flips that element's backward mask, producing isolated
    O(1e-3) gradient diffs that elementwise rtol cannot distinguish
    from real bugs (measured round 5: swapping ReLU for softplus in
    BOTH models collapses the worst per-leaf relative L2 from 4.3e-3
    to 6.3e-6). So this test runs two legs: a STRICT leg with a smooth
    activation (pure routing check, no flip noise — a scatter bug moves
    O(1) relative mass) and a loose leg on the real ReLU model."""
    import distributed_learning_simulator_tpu.models.resnet as resnet_mod

    x = np.asarray(
        jax.random.normal(jax.random.key(5), (4, 32, 32, 3), jnp.float32)
    )
    y = np.asarray(
        jax.random.randint(jax.random.key(6), (4,), 0, 10)
    )

    def worst_rel_l2():
        unfolded_model = ResNet18(fold_stage1=False, dtype=jnp.float32)
        folded_model = ResNet18(fold_stage1=True, dtype=jnp.float32)
        pu = unfolded_model.init(jax.random.key(0), x[:1])["params"]
        pf = _transplant(
            pu, folded_model.init(jax.random.key(0), x[:1])["params"]
        )

        def loss(model, p):
            logits = model.apply({"params": p}, x)
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

        gu = jax.grad(lambda p: loss(unfolded_model, p))(pu)
        gf = jax.grad(lambda p: loss(folded_model, p))(pf)
        # Compare via the same transplant mapping, in the folded tree's
        # shape.
        gu_in_folded = _transplant(gu, gf)
        worst = ("", 0.0)
        for (ku, lu), (kf, lf) in zip(
            sorted(jax.tree_util.tree_leaves_with_path(gu_in_folded),
                   key=lambda kv: str(kv[0])),
            sorted(jax.tree_util.tree_leaves_with_path(gf),
                   key=lambda kv: str(kv[0])),
        ):
            assert str(ku) == str(kf)
            a, b = np.asarray(lf), np.asarray(lu)
            rel = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-12)
            if rel > worst[1]:
                worst = (str(ku), float(rel))
        return worst

    # Strict leg: smooth activation in BOTH models — no ReLU-flip noise,
    # so any routing/duplication bug in the packing transpose shows as
    # O(1) relative mass against a ~1e-5 float noise floor.
    orig_relu = resnet_mod.nn.relu
    resnet_mod.nn.relu = jax.nn.softplus
    try:
        key, rel = worst_rel_l2()
        assert rel < 1e-4, (key, rel)
    finally:
        resnet_mod.nn.relu = orig_relu
    # Loose leg: the real ReLU model — bounds flip noise (isolated
    # elements at ~1e-3) while still far below a packing bug's O(1).
    key, rel = worst_rel_l2()
    assert rel < 2e-2, (key, rel)


def test_plain_group_norm_matches_flax():
    """PlainGroupNorm (closed-form backward) must match nn.GroupNorm in
    forward AND gradients (f32, tight tolerance) — it replaces it
    throughout the unfolded blocks under the same parameter names."""
    import flax.linen as nn

    from distributed_learning_simulator_tpu.models.resnet import (
        PlainGroupNorm,
    )

    x = jax.random.normal(jax.random.key(0), (4, 8, 8, 64), jnp.float32)
    y = np.asarray(jax.random.randint(jax.random.key(1), (4,), 0, 10))
    # bf16 (production dtype): agreement within output ulps — our affine
    # runs in f32 with ONE output cast, flax casts operands to bf16 first.
    ours16 = PlainGroupNorm(num_groups=32, dtype=jnp.bfloat16)
    ref16 = nn.GroupNorm(num_groups=32, dtype=jnp.bfloat16)
    p16 = ref16.init(jax.random.key(2), x)["params"]
    np.testing.assert_allclose(
        np.asarray(ours16.apply({"params": p16}, x), dtype=np.float32),
        np.asarray(ref16.apply({"params": p16}, x), dtype=np.float32),
        rtol=0.02, atol=0.02,
    )
    import pytest

    with pytest.raises(ValueError, match="must divide"):
        PlainGroupNorm(num_groups=32, dtype=jnp.float32).init(
            jax.random.key(0), jnp.zeros((1, 4, 4, 48), jnp.float32)
        )
    ours = PlainGroupNorm(num_groups=32, dtype=jnp.float32)
    ref = nn.GroupNorm(num_groups=32, dtype=jnp.float32)
    p_ours = ours.init(jax.random.key(2), x)["params"]
    p_ref = ref.init(jax.random.key(2), x)["params"]
    assert jax.tree_util.tree_structure(p_ours) == (
        jax.tree_util.tree_structure(p_ref)
    )
    # randomize params so grads through scale/bias are non-trivial
    p = jax.tree_util.tree_map(
        lambda l: l + 0.3 * jax.random.normal(jax.random.key(3), l.shape),
        p_ref,
    )
    np.testing.assert_allclose(
        np.asarray(ours.apply({"params": p}, x)),
        np.asarray(ref.apply({"params": p}, x)),
        rtol=1e-5, atol=1e-5,
    )

    def loss(module, params, inp):
        out = module.apply({"params": params}, inp)
        return jnp.sum(out * out) + jnp.sum(out[..., y])

    g_ours = jax.grad(lambda pp, xx: loss(ours, pp, xx), argnums=(0, 1))(p, x)
    g_ref = jax.grad(lambda pp, xx: loss(ref, pp, xx), argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(g_ours),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_gn_custom_backward_matches_autodiff():
    """The closed-form GN backward vs XLA autodiff of the SAME forward,
    through the whole folded model: gradients must agree tightly in f32
    (gn_custom_backward=False is the escape hatch --model_args exposes)."""
    x = np.asarray(
        jax.random.normal(jax.random.key(8), (2, 32, 32, 3), jnp.float32)
    )
    y = np.asarray(jax.random.randint(jax.random.key(9), (2,), 0, 10))
    custom = ResNet18(dtype=jnp.float32, gn_custom_backward=True)
    auto = ResNet18(dtype=jnp.float32, gn_custom_backward=False)
    p = custom.init(jax.random.key(0), x[:1])["params"]

    def loss(model, params):
        logits = model.apply({"params": params}, x)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    g_c = jax.grad(lambda pp: loss(custom, pp))(p)
    g_a = jax.grad(lambda pp: loss(auto, pp))(p)
    for a, b in zip(jax.tree_util.tree_leaves(g_c),
                    jax.tree_util.tree_leaves(g_a)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_folded_param_count_unchanged():
    """Folding changes layout only: identical total parameter count."""
    x = jnp.zeros((1, 32, 32, 3), jnp.float32)
    pu = ResNet18(fold_stage1=False).init(jax.random.key(0), x)["params"]
    pf = ResNet18(fold_stage1=True).init(jax.random.key(0), x)["params"]
    count = lambda t: sum(  # noqa: E731
        l.size for l in jax.tree_util.tree_leaves(t)
    )
    assert count(pu) == count(pf)


def test_model_args_escape_hatch_disables_fold(tiny_config):
    """config.model_args={"fold_stage1": False} reaches the constructor
    through run_simulation — the escape hatch that keeps pre-fold
    checkpoints resumable (ADVICE r3 medium)."""
    import dataclasses

    from distributed_learning_simulator_tpu.simulator import run_simulation

    cfg = dataclasses.replace(
        tiny_config, model_name="resnet18", worker_number=2, round=1,
        batch_size=8, n_train=64, n_test=32,
        dataset_args={"difficulty": 0.5, "shape": (32, 32, 3)},
        model_args={"fold_stage1": False},
    )
    res = run_simulation(cfg, setup_logging=False)
    assert not any("Folded" in k for k in res["global_params"])
    assert np.isfinite(res["history"][-1]["test_loss"])


def test_model_args_cli_json():
    """--model_args parses a JSON object from the CLI."""
    from distributed_learning_simulator_tpu.config import get_config

    cfg = get_config(
        ["--model_args", '{"fold_stage1": false}', "--log_level", "WARNING"]
    )
    assert cfg.model_args == {"fold_stage1": False}


def test_folded_resnet_trains(tiny_config):
    """End-to-end: the folded flagship model learns under the engine."""
    import dataclasses

    from distributed_learning_simulator_tpu.simulator import run_simulation

    cfg = dataclasses.replace(
        tiny_config, model_name="resnet18", worker_number=2, round=2,
        batch_size=8, n_train=64, n_test=32,
        dataset_args={"difficulty": 0.5, "shape": (32, 32, 3)},
    )
    res = run_simulation(cfg, setup_logging=False)
    assert np.isfinite(res["history"][-1]["test_loss"])


def test_pallas_gn_matches_jnp():
    """Pallas GroupNorm forward (ops/gn_pallas.py) vs the jnp form: stats
    to f32-reduction tolerance, outputs within one bf16 ulp. The suite
    pins the CPU backend (conftest), where the Mosaic kernels don't
    exist — on a TPU host run it past the conftest:
    ``python -m pytest --noconftest tests/test_folded_resnet.py -k pallas``
    (passed on a TPU v5e with jax 0.9.0 / libtpu 0.0.34, PR 21).
    """
    import pytest

    if jax.default_backend() != "tpu":
        pytest.skip("pallas GN kernels are Mosaic-only (suite runs on CPU)")
    import distributed_learning_simulator_tpu.models.resnet as R

    rng = np.random.default_rng(0)
    xf = jnp.asarray(
        rng.normal(size=(25, 32, 16, 128)).astype(np.float32) * 2 + 1.5,
        jnp.bfloat16,
    )
    scale = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
    # DLS_GN_PALLAS is frozen into a module constant at import (flipping
    # the env var mid-process could never outrun the jit cache); toggling
    # the constant is the supported way to exercise both kernels in-process.
    prev = R._GN_PALLAS_ENABLED
    try:
        R._GN_PALLAS_ENABLED = False
        y0, m0, r0 = R._fgn_forward(xf, scale, bias, 32, 1e-6, jnp.bfloat16)
        R._GN_PALLAS_ENABLED = True
        y1, m1, r1 = R._fgn_forward(xf, scale, bias, 32, 1e-6, jnp.bfloat16)
    finally:
        R._GN_PALLAS_ENABLED = prev
    np.testing.assert_allclose(
        np.asarray(m1.reshape(-1)), np.asarray(m0.reshape(-1)), rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(r1.reshape(-1)), np.asarray(r0.reshape(-1)), rtol=1e-5
    )
    d = np.abs(
        np.asarray(y1, np.float32) - np.asarray(y0, np.float32)
    )
    # one output ulp at these magnitudes
    assert d.max() <= 0.0625, d.max()


def test_pallas_gn_requested_off_tpu_is_an_error(monkeypatch):
    """DLS_GN_PALLAS=1 must not hand back the jnp path in silence on a
    backend that has no Mosaic."""
    import pytest

    import distributed_learning_simulator_tpu.models.resnet as R

    monkeypatch.setattr(R, "_GN_PALLAS_ENABLED", True)
    with pytest.raises(RuntimeError, match="only on TPU"):
        R._use_pallas_gn()
