"""AFMoE / Trinity-Mini (models/afmoe.py) against its plain reference
(benchmark/references/afmoe.py) at a tiny preset on the CPU: hidden 64, 8
query heads of 16 over 2 key/value heads, window 32 in query blocks of 16,
16 experts top-2 of which 2-4 are held, one dense layer and a period
``[sliding, sliding, sliding, full]``, seeded weights, float32 products.
The reference masks all the keys; the program reads a band of them, groups
the routed tokens by expert and batches the sequences."""

import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_learning_simulator_tpu.models import afmoe as af
from distributed_learning_simulator_tpu.models import lm_parts as parts
from distributed_learning_simulator_tpu.models.registry import get_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HI = jax.lax.Precision.HIGHEST


def _module_at(*path):
    path = os.path.join(ROOT, *path) + ".py"
    spec = importlib.util.spec_from_file_location(
        "_t_afmoe_" + os.path.basename(path)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _module_at("benchmark", "references", "afmoe")

MODEL = {
    "hidden_size": 64, "num_hidden_layers": 5, "num_dense_layers": 1,
    "layer_types": ["sliding_attention"] * 4 + ["full_attention"],
    "head_dim": 16, "num_attention_heads": 8, "num_key_value_heads": 2,
    "sliding_window": 32, "rope_theta": 10000.0, "intermediate_size": 96,
    "num_experts": 16, "experts_held": 4, "expert_offset": 0,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "route_scale": 2.826, "rms_norm_eps": 1e-5, "vocab_rows": 96,
}
PRODUCTS = {
    "dense": lambda x, w: jnp.dot(x, w, precision=HI),
    "q": lambda a: a, "precision": HI,
}


def share_args(model=MODEL, **over):
    args = {k: v for k, v in model.items() if k != "vocab_rows"}
    return {**args, "dtype": "float32", "query_block": 16, **over}


def share(model=MODEL, **over):
    args = share_args(model, **over)
    return af.Share(**{**args, "layer_types": tuple(args["layer_types"])})


def make_params(model=MODEL, seed=0, bias_std=0.01):
    """Seeded weights from the reference's layout; the norms' scales are
    moved off 1 so every parameter has a gradient that could be wrong."""
    rng = np.random.default_rng(seed)
    tree = {}
    for path, (shape, kind) in sorted(ref.layout(model, None).items()):
        if kind == "ones":
            leaf = 1.0 + 0.1 * rng.standard_normal(shape)
        elif isinstance(kind, dict) and "std" in kind:
            std = bias_std if path[-1] == "bias" else kind["std"]
            leaf = std * rng.standard_normal(shape)
        else:
            fan_in = (kind["fan_in"] if isinstance(kind, dict)
                      else int(np.prod(shape[:-1])))
            leaf = rng.standard_normal(shape) / np.sqrt(fan_in)
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = jnp.asarray(leaf, jnp.float32)
    return tree


def close(got, want, tol=2e-4):
    scale = float(jnp.max(jnp.abs(want))) + 1e-12
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


def tree_close(got, want, tol=2e-4):
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        gap = float(jnp.max(jnp.abs(g - w)))
        assert gap <= tol * scale, (jax.tree_util.keystr(path), gap, scale)


def grads(fn, p, x):
    return jax.grad(lambda p, x: jnp.sum(jnp.sin(fn(p, x))),
                    argnums=(0, 1))(p, x)


def _inputs(length, seed=1):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal((2, length, 64)),
        jnp.float32)


def _program_moe(p, x, c):
    tokens = x.reshape(-1, x.shape[-1])
    combine = af.moe_route(p, tokens, c)
    routed, load, overflow = af.moe_experts(
        p, tokens, combine, dtype=jnp.float32,
        capacity=parts.expert_capacity(
            tokens.shape[0], c.num_experts_per_tok, c.num_experts))
    y = routed + af.moe_shared(p, tokens, dtype=jnp.float32)
    return y.reshape(x.shape), load, overflow


@pytest.fixture(params=["slots", "ragged"])
def expert_form(request, monkeypatch):
    """Both forms of the held experts' product at the tiny size: slots of
    each expert's own (what the size would choose), and the ragged
    product over rows the experts share (what the cell's 1,024 slots an
    expert choose; here chosen by lowering the threshold)."""
    if request.param == "ragged":
        monkeypatch.setattr(parts, "RAGGED_MIN_SLOTS", 8)
    return request.param


LAYERS = {  # kind -> (layer of the stack, its parameter group)
    "sliding": ("layer_1", "attn"), "full": ("layer_4", "attn"),
    "dense": ("layer_0", "mlp"), "expert": ("layer_2", "moe"),
}


def _program_layer(kind, p, x, c):
    if kind == "sliding":
        return af.swa(p, x, c)
    if kind == "full":
        return af.attn_full(p, x, c)
    if kind == "dense":
        return af.mlp_dense(p, x, dtype=jnp.float32)
    return _program_moe(p, x, c)[0]


def _reference_layer(kind, p, x, model=MODEL):
    if kind in ("sliding", "full"):
        return ref.attention(model, p, x, **PRODUCTS,
                             sliding=kind == "sliding")
    if kind == "dense":
        return ref.swiglu(x, p["gate"], p["up"], p["down"],
                          PRODUCTS["dense"])
    return ref.moe(model, p, x, **PRODUCTS)


def test_expert_layer_matches_the_reference_in_both_forms(expert_form):
    test_layer_matches_the_reference("expert")


@pytest.mark.parametrize("kind", ["sliding", "full", "dense", "expert"])
def test_layer_matches_the_reference(kind):
    layer, group = LAYERS[kind]
    p, x, c = make_params(seed=1)[layer][group], _inputs(80), share()

    def program(p, x):
        return _program_layer(kind, p, x, c)

    def plain(p, x):
        return _reference_layer(kind, p, x)

    close(program(p, x), plain(p, x))
    tree_close(grads(program, p, x), grads(plain, p, x), 5e-4)


@pytest.mark.parametrize("length,window,block", [
    (100, 32, 16),   # several windows, not a multiple of the block
    (40, 64, 16),    # shorter than the window
    (128, 32, 16),   # four windows, whole blocks
    (72, 24, 16),    # a window that is not a multiple of the block
    (50, 32, 64),    # one block: every key is read
])
def test_the_band_is_the_mask(length, window, block):
    """A block of queries reads only the keys of its band; the result
    and every gradient are those of the mask over ALL the keys."""
    model = {**MODEL, "sliding_window": window}
    p, x = make_params(model, seed=2)["layer_1"]["attn"], _inputs(length, 2)
    c = share(model, query_block=block)
    keys = parts.band_keys(length, window, block)
    n = -(-length // min(block, length))
    assert keys == min(block, length) + min(
        window, (n - 1) * min(block, length))
    assert keys <= max(length, block + window)

    def program(p, x):
        return af.swa(p, x, c)

    def plain(p, x):
        return ref.attention(model, p, x, **PRODUCTS, sliding=True)

    close(program(p, x), plain(p, x))
    tree_close(grads(program, p, x), grads(plain, p, x), 5e-4)


def test_a_banded_block_is_never_handed_a_key_outside_its_band():
    """In the traced program no product of scores is wider than the band:
    the keys are sliced before the loop over blocks, not masked."""
    p, x = make_params(seed=2)["layer_1"]["attn"], _inputs(128, 2)
    c = share()
    jaxpr = jax.make_jaxpr(lambda p, x: af.swa(p, x, c))(p, x)
    text = str(jaxpr)
    assert "16,48]" in text  # scores [.., block 16, band 48]
    assert "16,128]" not in text  # never a block against every key
    full = str(jax.make_jaxpr(lambda p, x: af.attn_full(p, x, c))(p, x))
    assert "16,128]" in full


@pytest.mark.parametrize("length,block", [(96, 40), (70, 32), (30, 64)])
def test_attention_pads_its_last_block_of_queries(length, block):
    """A length that is not a multiple of the query block runs in blocks
    all the same (the last one padded), never as one block of every
    query: at 8,192 positions and 32 heads that one block's scores would
    be 8.6 GB."""
    p, x = make_params(seed=3)["layer_4"]["attn"], _inputs(length, 3)
    c = share(query_block=block)
    close(af.attn_full(p, x, c),
          ref.attention(MODEL, p, x, **PRODUCTS, sliding=False))
    text = str(jax.make_jaxpr(lambda p, x: af.attn_full(p, x, c))(p, x))
    if length > block:
        assert f"{length},{length}]" not in text
    tree_close(
        grads(lambda p, x: af.attn_full(p, x, c), p, x),
        grads(lambda p, x: ref.attention(
            MODEL, p, x, **PRODUCTS, sliding=False), p, x), 5e-4)


def test_the_selection_bias_selects_and_is_not_trained():
    """The bias moves some choices and not most, weighs nothing, and has
    no gradient."""
    p, x = make_params(seed=4, bias_std=0.05)["layer_2"]["moe"], _inputs(96)
    index, weight = ref.route(MODEL, p, x, PRODUCTS["dense"])
    plain_index, _ = ref.route(
        MODEL, {**p, "bias": jnp.zeros_like(p["bias"])}, x,
        PRODUCTS["dense"])
    moved = float(jnp.mean(jnp.sort(index) != jnp.sort(plain_index)))
    assert 0.0 < moved < 0.5
    np.testing.assert_allclose(jnp.sum(weight, -1), MODEL["route_scale"],
                               rtol=1e-5)
    c = share()
    g, _ = grads(lambda p, x: _program_moe(p, x, c)[0], p, x)
    assert float(jnp.max(jnp.abs(g["bias"]))) == 0.0
    assert float(jnp.max(jnp.abs(g["router"]))) > 0.0


@pytest.mark.parametrize("factor,overflows", [(1.5, False), (0.25, True)])
def test_no_token_is_dropped_whatever_the_capacity(
        factor, overflows, monkeypatch, expert_form):
    """Slots of an expert's own overflow when ONE expert is chosen by
    more than its slots, shared rows when all the held experts' choosers
    together outnumber the rows; either way every token is computed."""
    p, x = make_params(seed=5)["layer_2"]["moe"], _inputs(96, 5)
    monkeypatch.setattr(parts, "EXPERT_CAPACITY_FACTOR", factor)
    c = share()
    assert parts.expert_capacity(2 * 96, 2, 16) < 2 * 96
    y, load, overflow = _program_moe(p, x, c)
    close(y, ref.moe(MODEL, p, x, **PRODUCTS))
    assert bool(overflow) is overflows
    index, _ = ref.route(MODEL, p, x, PRODUCTS["dense"])
    assert load.tolist() == [int(jnp.sum(index == e)) for e in range(4)]


def test_whole_model_loss_and_gradients(expert_form):
    params = make_params(seed=3)
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, 96, (2, 72)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    model = get_model("afmoe", num_classes=96, vocab_rows=96,
                      **share_args())

    def loss(logits):
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(
            jnp.take_along_axis(logp, targets[..., None], -1))

    def program(params):
        head, counts = model.apply({"params": params}, tokens)
        return loss(head.logits()), counts

    def plain(params):
        return loss(ref.forward(MODEL, params, tokens, **PRODUCTS))

    (got, counts), got_grads = jax.value_and_grad(
        program, has_aux=True)(params)
    want, want_grads = jax.value_and_grad(plain)(params)
    assert abs(float(got) - float(want)) < 1e-4 * float(want)
    tree_close(got_grads, want_grads, 1e-3)
    # Counters of the EXPERT layers only: 4 of 5.
    assert counts["moe_routed_tokens"].tolist() == [144] * 4
    assert counts["moe_expert_load"].shape == (4, 4)
    # The flax module declares exactly the reference's layout.
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), tokens)["params"])
    assert {
        tuple(k.key for k in path): leaf.shape
        for path, leaf in jax.tree_util.tree_leaves_with_path(shapes)
    } == {
        path: tuple(shape) for path, (shape, _) in
        ref.layout(MODEL, None).items()
    }


def test_the_training_loss_is_the_references():
    """The engine's loss over the head that makes its own
    (``weighted_nll``) against the task's loss over the reference's
    logits, and its gradients."""
    from distributed_learning_simulator_tpu.parallel.engine import (
        make_loss_fn)

    params = make_params(seed=6)
    tokens = jnp.asarray(
        np.random.default_rng(6).integers(0, 96, (2, 40)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    model = get_model("afmoe", num_classes=96, **share_args())

    def plain(params):
        logp = jax.nn.log_softmax(
            ref.forward(MODEL, params, tokens, **PRODUCTS))
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))

    got, got_grads = jax.value_and_grad(
        lambda p: make_loss_fn(model.apply)(
            p, tokens, targets, jnp.ones((2,)))[0])(params)
    want, want_grads = jax.value_and_grad(plain)(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    tree_close(got_grads, want_grads, 1e-3)


def test_the_shares_add_up_to_the_uncut_layer(expert_form):
    """model-configs § 4: over all 8 shares of the experts (2 of 16
    each) the parts of the layer's result add up, with what every chip
    computes alike (the shared expert) counted once, to what the uncut
    reference gives for the whole layer; so do 4 shares of 4."""
    whole = {**MODEL, "experts_held": 16}
    p, x = make_params(whole, seed=7)["layer_2"]["moe"], _inputs(56, 7)
    want = ref.moe(whole, p, x, **PRODUCTS)
    only_shared = ref.moe({**whole, "experts_held": 0}, p, x, **PRODUCTS)
    for held in (2, 4):
        total = 0.0
        for offset in range(0, 16, held):
            mine = {**p, **{k: p[k][offset:offset + held]
                            for k in ("gate", "up", "down")}}
            c = share(experts_held=held, expert_offset=offset)
            total = total + _program_moe(mine, x, c)[0]
        close(total - (16 // held - 1) * only_shared, want)


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def test_every_product_is_traced_from_the_line_of_a_scope():
    """The shared implementations (models/lm_parts.py) are registered as
    not being user code, so every matrix product of the loss and of its
    gradient carries a line of THIS model's scope functions."""
    from jax._src import source_info_util

    from distributed_learning_simulator_tpu.parallel.engine import (
        make_loss_fn)

    model = get_model("afmoe", num_classes=96, **share_args())
    params = make_params()
    tokens = jnp.zeros((2, 48), jnp.int32)

    def scopes_of_products(jaxpr):
        scopes = set()
        for eqn in _equations(jaxpr):
            if eqn.primitive.name != "dot_general":
                continue
            frame = source_info_util.user_frame(eqn.source_info.traceback)
            assert frame.file_name.endswith("models/afmoe.py"), frame
            scope = af.scope_of_line(frame.start_line)
            assert scope is not None, frame
            scopes.add(scope)
        return scopes

    def loss(p):
        head, _ = model.apply({"params": p}, tokens)
        return jnp.sum(head.logits())

    assert scopes_of_products(
        jax.make_jaxpr(jax.grad(loss))(params).jaxpr) == set(af._SCOPES)
    train = jax.grad(lambda p: make_loss_fn(model.apply)(
        p, tokens, tokens, jnp.ones((2,)))[0])
    assert scopes_of_products(
        jax.make_jaxpr(train)(params).jaxpr) == set(af._SCOPES)
    assert set(af._SCOPES) == {
        "swa", "attn_full", "mlp_dense", "moe/route", "moe/experts",
        "moe/shared", "lm_head"}
    assert af.scope_of_line(af.swa.__code__.co_firstlineno + 2) == "swa"
    assert af.scope_of_line(af.afmoe.__code__.co_firstlineno) is None


def test_share_refuses_what_it_cannot_hold():
    with pytest.raises(ValueError, match="layer_types"):
        af.afmoe(96, layer_types=["sliding_attention"])
    with pytest.raises(ValueError, match="layer_types"):
        af.afmoe(96, num_hidden_layers=1, layer_types=["windowed"])
    with pytest.raises(ValueError, match="groups"):
        af.afmoe(96, num_attention_heads=6)
    model = af.afmoe(96, vocab_rows=64, **share_args())
    with pytest.raises(ValueError, match="vocab_rows"):
        model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))


def test_the_size_chooses_the_form_of_the_experts_product(monkeypatch):
    """1,024 slots an expert (the 8k cell: 8,192 tokens, top-8 of 128)
    share their rows in one ragged product; 256 (Solar-Open2's cell:
    4,096 tokens, top-8 of 320) stay slots of each expert's own."""
    assert parts.expert_capacity(8192, 8, 128) == 1024
    assert parts.expert_capacity(4096, 8, 320) == 256
    assert parts.expert_capacity(4096, 8, 320) < parts.RAGGED_MIN_SLOTS <= (
        parts.expert_capacity(8192, 8, 128))
    p = make_params(seed=8)["layer_2"]["moe"]
    tokens = _inputs(96, 8).reshape(-1, 64)
    combine = af.moe_route(p, tokens, share())

    def ragged(slots):
        return "ragged_dot" in str(jax.make_jaxpr(lambda x: parts.experts(
            p, x, combine, capacity=slots, dtype=jnp.float32)[0])(tokens))

    monkeypatch.setattr(parts, "RAGGED_MIN_SLOTS", 32)
    assert ragged(32) and not ragged(24)


def _rows_past_the_groups_hold_nan(product):
    """``product`` (``lm_parts._ragged``) as the TPU's kernel leaves it:
    the rows past the last group hold NaN in its result and in the
    gradient of its rows (the CPU's ``ragged_dot`` writes zeros there)."""

    def poison(rows, sizes):
        real = jnp.arange(rows.shape[0]) < jnp.sum(sizes)
        return jnp.where(real[:, None], rows, jnp.nan)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def poisoned(rows, kernels, sizes, dtype):
        return poison(product(rows, kernels, sizes, dtype), sizes)

    def forward(rows, kernels, sizes, dtype):
        return poisoned(rows, kernels, sizes, dtype), (rows, kernels, sizes)

    def backward(dtype, residuals, ct):
        rows, kernels, sizes = residuals
        _, vjp = jax.vjp(lambda r, k: product(r, k, sizes, dtype), rows,
                         kernels)
        d_rows, d_kernels = vjp(ct)
        return poison(d_rows, sizes), d_kernels, None

    poisoned.defvjp(forward, backward)
    return poisoned


def test_the_kernels_unwritten_rows_are_never_read(monkeypatch):
    """PR 33's fault, which only the chip showed: NaN in the rows the
    ragged kernel leaves unwritten reached token 0. With those rows NaN
    here too, the ragged form's output and the gradients of the tokens,
    the combine weights (and the router, through them) and the three
    kernels are finite and the reference's."""
    monkeypatch.setattr(parts, "RAGGED_MIN_SLOTS", 8)
    monkeypatch.setattr(parts, "_ragged",
                        _rows_past_the_groups_hold_nan(parts._ragged))
    p, x = make_params(seed=9)["layer_2"]["moe"], _inputs(100, 9)
    tokens, c = x.reshape(-1, 64), share()
    capacity = parts.expert_capacity(200, 2, 16)
    assert parts.RAGGED_MIN_SLOTS <= capacity < 200
    kernels = ("gate", "up", "down")

    def program(x, combine, *kernel):
        return parts.experts(dict(zip(kernels, kernel)), x, combine,
                             capacity=capacity, dtype=jnp.float32)[0]

    def reference(x, combine, *kernel):
        return sum(combine[:, e, None] * ref.swiglu(
            x, *(k[e] for k in kernel), PRODUCTS["dense"]) for e in range(4))

    combine = af.moe_route(p, tokens, c)
    assert 0 < int(jnp.sum(combine > 0)) < 4 * capacity
    args = (tokens, combine) + tuple(p[k] for k in kernels)
    y = program(*args)
    assert bool(jnp.all(jnp.isfinite(y)))
    close(y, reference(*args))
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(program(*a))),
                   argnums=range(5))(*args)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(reference(*a))),
                    argnums=range(5))(*args)
    chosen = combine > 0  # a pair no token chose has no weight to move
    got, want = ((a[0], jnp.where(chosen, a[1], 0.0), *a[2:])
                 for a in (got, want))
    for g, w in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(g)))
        close(g, w, 1e-3)

    def routed(p, x):  # the model's path, the router's gradient too
        return parts.experts(p, x, af.moe_route(p, x, c), capacity=capacity,
                             dtype=jnp.float32)[0]

    def plain(p, x):
        return ref.moe(MODEL, p, x.reshape(2, 100, 64), **PRODUCTS,
                       shared=False).reshape(x.shape)

    close(routed(p, tokens), plain(p, tokens))
    got, want = grads(routed, p, tokens), grads(plain, p, tokens)
    assert all(bool(jnp.all(jnp.isfinite(g)))
               for g in jax.tree_util.tree_leaves(got))
    tree_close(got, want, 1e-3)


def test_the_ragged_form_moves_its_tokens_by_a_permutation(monkeypatch):
    """The ragged branch, forward and gradient, selects over no row-sized
    operand (``[held x capacity, D]``, ``[held x capacity, F]``: padding
    rows name token N, which the gather reads as token N - 1 and the
    scatter-add drops) and scatters no ``N x held`` (token, expert)
    pairs."""
    monkeypatch.setattr(parts, "RAGGED_MIN_SLOTS", 8)
    p, x = make_params(seed=10)["layer_2"]["moe"], _inputs(100, 10)
    tokens, c = x.reshape(-1, 64), share()
    n, held = tokens.shape[0], c.experts_held
    capacity = parts.expert_capacity(n, 2, 16)
    rows = held * capacity
    assert len({rows, n}) == 2

    def layer(p, x):
        return parts.experts(p, x, af.moe_route(p, x, c), capacity=capacity,
                             dtype=jnp.float32)[0]

    for fn in (layer, jax.grad(lambda p, x: jnp.sum(layer(p, x)),
                               argnums=(0, 1))):
        eqns = list(_equations(jax.make_jaxpr(fn)(p, tokens).jaxpr))
        assert any(e.primitive.name == "ragged_dot_general" for e in eqns)
        for e in eqns:
            shapes = {tuple(v.aval.shape) for v in e.invars
                      if hasattr(v.aval, "shape")}
            if e.primitive.name == "select_n":
                assert not shapes & {(rows, 64), (rows, 32)}, shapes
            if e.primitive.name.startswith("scatter"):
                assert e.invars[2].aval.size != n * held, shapes


def test_window_counters():
    model = af.afmoe(96, **share_args())
    assert model.attention_window == 32
    assert model.swa_keys_per_query_block(128) == 16 + 32
    assert model.swa_keys_per_query_block(12) == 12  # one block: all keys
    full = af.afmoe(96, **share_args(
        num_hidden_layers=1, num_dense_layers=1,
        layer_types=["full_attention"]))
    assert full.attention_window == 0
    assert full.swa_keys_per_query_block(128) == 0
    # The cell's: a block of 128 queries reads 2,176 keys of 8,192.
    assert af.afmoe(25024).swa_keys_per_query_block(8192) == 2176


# --- the fused kernel ---------------------------------------------------------

#: The kernel at its smallest blocks: 256 positions are two of them.
_SMALL_BLOCKS = parts.FusedBlocks(128, 128, 128)


def _qkv(batch, length, kv, group, head_dim, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    return (
        jax.random.normal(keys[0], (batch, length, kv, group, head_dim)),
        jax.random.normal(keys[1], (batch, length, kv, head_dim)),
        jax.random.normal(keys[2], (batch, length, kv, head_dim)),
        jax.random.normal(keys[3], (batch, length, kv, group, head_dim)),
    )


def _xla_attention(window, dtype=jnp.bfloat16, block=128):
    if window is None:
        return lambda q, k, v: parts.causal_attention(
            q, k, v, dtype=dtype, query_block=block)
    return lambda q, k, v: parts.banded_attention(
        q, k, v, window=window, dtype=dtype, query_block=block)


@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("group", [1, 8])
@pytest.mark.parametrize("window", [None, 96, 300],
                         ids=["causal", "window<T", "window>=T"])
def test_the_fused_kernel_is_the_xla_form(window, group, batch):
    """The splash kernel (Pallas's interpreter: the CPU has no Mosaic)
    against ``causal_attention`` / ``banded_attention`` with products in
    bf16: the outputs to a bf16 step of the largest value, each of the
    three gradients to 2 % of its norm (measured 0.4-0.6 %: the kernel
    rounds its output and the scaled queries to bf16, the XLA form
    neither)."""
    q, k, v, w = _qkv(batch, 256, 2, group, 128)
    want, want_vjp = jax.vjp(_xla_attention(window), q, k, v)
    got, got_vjp = jax.vjp(
        lambda q, k, v: parts.fused_attention(
            q, k, v, window=window, dtype=jnp.bfloat16,
            blocks=_SMALL_BLOCKS, interpret=True), q, k, v)
    assert got.shape == want.shape and got.dtype == want.dtype
    close(got, want, 2 ** -7)
    for g, r in zip(got_vjp(w), want_vjp(w)):
        assert g.shape == r.shape
        assert float(jnp.linalg.norm(g - r)) <= 0.02 * float(
            jnp.linalg.norm(r))


def test_the_fused_kernel_is_built_once_a_shape():
    """One kernel (its mask's block tables are made on the host) per
    (positions, window, group, blocks), whatever the layer or the trace
    that asks, and what is kept holds no tracer."""
    q, k, v, _ = _qkv(1, 128, 1, 2, 128)

    def run(q, k, v):
        return parts.fused_attention(
            q, k, v, window=64, dtype=jnp.bfloat16, blocks=_SMALL_BLOCKS,
            interpret=True)

    parts._fused_kernel.cache_clear()
    first = jax.jit(lambda q, k, v: run(q, k, v) + run(q, k, v))(q, k, v)
    again = jax.jit(run)(q, k, v)  # another trace, the same kernel
    info = parts._fused_kernel.cache_info()
    assert (info.misses, info.hits) == (1, 2)
    assert jnp.array_equal(first, 2 * again)


@pytest.mark.parametrize("length,head_dim,dtype", [
    (300, 128, "bfloat16"),   # no whole number of the kernel's blocks
    (1024, 64, "bfloat16"),   # half the lanes
    (1024, 128, "float32"),   # the kernel would multiply f32 in one bf16 pass
], ids=["length", "head_dim", "dtype"])
def test_other_shapes_keep_the_xla_form(length, head_dim, dtype):
    """Shapes the kernel does not take trace to the XLA form and nothing
    else (no choice by platform is left in the program) and give its
    numbers digit for digit, a padded last block included."""
    assert not parts.fused_attention_applies(length, head_dim, dtype)
    q, k, v, w = _qkv(1, length, 1, 2, head_dim)
    dtype = jnp.dtype(dtype)

    def core(q, k, v):
        return parts.attention_core(q, k, v, window=None, dtype=dtype,
                                    query_block=128)

    plain = _xla_attention(None, dtype)
    assert "platform_index" not in str(jax.make_jaxpr(core)(q, k, v))

    def text(fn):  # less the line that names the module after ``fn``
        return jax.jit(fn).lower(q, k, v).as_text().split("\n", 1)[1]

    assert text(core) == text(plain)
    got, got_vjp = jax.vjp(core, q, k, v)
    want, want_vjp = jax.vjp(plain, q, k, v)
    assert jnp.array_equal(got, want)
    for g, r in zip(got_vjp(w), want_vjp(w)):
        assert jnp.array_equal(g, r)


@pytest.mark.parametrize("window", [None, 200], ids=["causal", "band"])
def test_the_lowering_platform_chooses_the_form(window):
    """Shapes the kernel takes: the traced program holds both forms and
    the LOWERING says which. For the CPU (the tests' platform, whatever
    ``jax.default_backend()`` would say elsewhere) no Pallas call is
    lowered and values and gradients are the XLA form's digit for digit;
    lowered for a TPU, with no TPU and no libtpu, the same trace holds
    the kernel's custom calls (forward, backward) and no softmax of a
    block's scores."""
    length = parts.FUSED_BLOCKS.q
    assert parts.fused_attention_applies(length, 128, "bfloat16")
    q, k, v, w = _qkv(1, length, 1, 1, 128)

    def core(q, k, v):
        return parts.attention_core(q, k, v, window=window,
                                    dtype=jnp.bfloat16, query_block=128)

    def loss(f):
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(f(q, k, v) * w), argnums=(0, 1, 2)))

    traced = loss(core).trace(q, k, v)
    assert "platform_index" in str(traced.jaxpr)
    cpu = traced.lower(lowering_platforms=("cpu",)).as_text()
    assert "tpu_custom_call" not in cpu
    tpu = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert tpu.count("tpu_custom_call") == 2
    assert "stablehlo.exponential" in cpu
    assert "stablehlo.exponential" not in tpu  # the softmax is the kernel's
    plain = _xla_attention(window)
    assert jnp.array_equal(jax.jit(core)(q, k, v), jax.jit(plain)(q, k, v))
    for g, r in zip(loss(core)(q, k, v), loss(plain)(q, k, v)):
        assert jnp.array_equal(g, r)


#: Positions of the models lowered below: one block of the kernel.
_POSITIONS = parts.FUSED_BLOCKS.q


def _lm(name):
    """Two layers of each language model at head 128 (over ``_POSITIONS``:
    shapes the kernel takes) and at the tests' tiny preset (shapes it
    does not)."""
    if name == "afmoe":
        big = get_model("afmoe", num_classes=96, **share_args(
            num_hidden_layers=2, layer_types=["sliding_attention",
                                              "full_attention"],
            head_dim=128, num_attention_heads=4, num_key_value_heads=2,
            sliding_window=256, dtype="bfloat16"))
        return big, get_model("afmoe", num_classes=96, **share_args())
    solar = dict(
        hidden_size=64, num_hidden_layers=2, gqa_layers=[0], head_dim=16,
        num_attention_heads=8, num_key_value_heads=4, heads_held=4,
        n_routed_experts=8, experts_held=4, num_experts_per_tok=2,
        moe_intermediate_size=32, gate_rank=8, dtype="float32")
    # Four query heads a key/value head where the other model has two:
    # JAX keeps the kernel's traced body by its shapes, lines and all, so
    # in ONE process a second model of the same attention shapes would
    # show the first one's lines (a run holds one model).
    big = get_model("solar_open2", num_classes=96, **{
        **solar, "head_dim": 128, "dtype": "bfloat16",
        "num_key_value_heads": 2})
    return big, get_model("solar_open2", num_classes=96, **solar)


def _loss_program(model, length):
    tokens = jnp.zeros((1, length), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), tokens)["params"])

    def loss(p, t):
        head, _ = model.apply({"params": p}, t)
        return head.weighted_nll(t, jnp.ones(t.shape))[0]

    return jax.jit(jax.grad(loss)).trace(params, tokens)


@pytest.mark.parametrize("name", ["afmoe", "solar_open2"])
def test_a_models_loss_lowers_by_platform(name, monkeypatch):
    """A model's loss and gradient, its blocks under ``nn.remat`` (no
    room on the device): at shapes the kernel takes the CPU's text holds
    no Pallas call and the TPU's holds the kernel once a pass of each
    attention layer (forward, the rematerialised forward, backward), and
    one fewer where the blocks keep their residuals (the CPU's 16 GiB:
    no recomputed forward); at the tiny preset the text is the one the
    XLA form alone lowers to (the parent's program)."""
    big, tiny = _lm(name)
    layers = big.fused_attention_layers(_POSITIONS)
    assert layers == (2 if name == "afmoe" else 1)
    assert _loss_program(big, _POSITIONS).lower(
        lowering_platforms=("tpu",)).as_text().count(
            "tpu_custom_call") == 2 * layers
    monkeypatch.setattr(parts, "device_memory_limit", lambda: (0, "none"))
    traced = _loss_program(big, _POSITIONS)
    assert "tpu_custom_call" not in traced.lower(
        lowering_platforms=("cpu",)).as_text()
    assert traced.lower(lowering_platforms=("tpu",)).as_text().count(
        "tpu_custom_call") == 3 * layers
    # JAX counts its Pallas ops as user code; the kernel's equations are
    # given the scope's line all the same, or no scope's device time
    # would hold the kernel.
    from jax._src import source_info_util

    module = importlib.import_module(type(big).__module__)
    scopes = [
        module.scope_of_line(source_info_util.user_frame(
            eqn.source_info.traceback).start_line)
        for eqn in _equations(traced.jaxpr.jaxpr)
        if eqn.primitive.name == "pallas_call"
        and source_info_util.user_frame(
            eqn.source_info.traceback).file_name == module.__file__
    ]
    assert len(scopes) == 3 * layers
    assert set(scopes) == (
        {"swa", "attn_full"} if name == "afmoe" else {"gqa"})
    assert tiny.fused_attention_layers(80) == 0
    text = _loss_program(tiny, 80).lower().as_text()

    def parents(q, k, v, *, window, dtype, query_block):
        return _xla_attention(window, dtype, query_block)(q, k, v)

    monkeypatch.setattr(parts, "attention_core", parents)
    assert _loss_program(tiny, 80).lower().as_text() == text


@pytest.mark.parametrize("model,positions,layers", [
    (lambda: af.afmoe(25024), 8192, 5),       # trinity_mini_fed_seq8k_c4
    (lambda: get_model("solar_open2", num_classes=24576), 4096, 1),
    (lambda: get_model("resnet18", num_classes=10), 3072, 0),
    (lambda: af.afmoe(25024), 8192 + 512, 0),  # no whole blocks
    (lambda: af.afmoe(25024, head_dim=64), 8192, 0),
    (lambda: af.afmoe(25024, dtype="float32"), 8192, 0),
], ids=["trinity_cell", "solar_cell", "image_cell", "length", "head_dim",
        "dtype"])
def test_fused_attention_layers_by_shape(model, positions, layers):
    """The count behind the recorder's ``fused_attention_layers`` (what
    ``run_simulation`` asks the model, and reports on a TPU only): the
    three configurations' shapes read 5, 1 and 0."""
    count = getattr(model(), "fused_attention_layers", lambda _: 0)
    assert count(positions) == layers


# --- through run_simulation --------------------------------------------------


def _token_dataset(seed=0, n_train=8, n_test=4, length=80):
    from distributed_learning_simulator_tpu.data.registry import Dataset

    rng = np.random.default_rng(seed)
    x = rng.integers(0, 96, (n_train + n_test, length + 1)).astype(np.int32)
    return Dataset("tokens", x[:n_train, :-1], x[:n_train, 1:],
                   x[n_train:, :-1], x[n_train:, 1:], 96)


def test_one_client_in_flight_through_run_simulation(tmp_path):
    """The model through the normal path under the benchmark's traffic
    flags: the loss falls, the counters say the band ran and what the four
    expert layers were sent, one host sync a round."""
    from distributed_learning_simulator_tpu.config import get_config
    from distributed_learning_simulator_tpu.simulator import run_simulation
    from distributed_learning_simulator_tpu.telemetry import spans

    argv = [
        "--dataset_name", "tokens", "--model_name", "afmoe",
        "--model_args", json.dumps(share_args()),
        "--worker_number", "4", "--epoch", "1", "--batch_size", "1",
        "--round", "2", "--client_chunk_size", "1",
        "--pipeline_rounds", "false",
        "--eval_batch_size", "2", "--optimizer_name", "sgd",
        "--learning_rate", "0.1", "--momentum", "0",
        "--distributed_algorithm", "fed", "--telemetry_level", "basic",
        "--log_root", str(tmp_path / "log"),
        "--compilation_cache_dir", "none",
    ]
    result = run_simulation(get_config(argv), dataset=_token_dataset())
    counts = spans.last_run().counters()
    history = result["history"]
    assert history[1]["test_loss"] < history[0]["test_loss"]
    assert counts["attention_window"] == 32
    assert counts["swa_keys_per_query_block"] == 48
    assert counts["fused_attention_layers"] == 0  # the CPU: the XLA form
    assert counts["remat_blocks"] == 0  # 16 GiB: every block keeps
    assert counts["head_backward_tied"] == 1
    assert counts["client_axis_width"] == 1
    assert counts["global_donated"] == 1
    # 2 rounds x 4 clients x 2 steps x 80 positions, an EXPERT layer (4).
    assert counts["routed_tokens"] == 4 * 2 * 4 * 2 * 80
    per_token = counts["local_expert_assignments"] / counts["routed_tokens"]
    assert 0.3 < per_token < 0.7  # top-2 of 16, 4 held: 0.5 expected
    assert counts["host_syncs"] == counts["rounds"] == 2
    load = history[-1]["expert_load"]
    assert np.asarray(load["load"]).shape == (4, 4)


def test_models_without_a_window_report_none(tmp_path):
    """``attention_window`` and ``swa_keys_per_query_block`` read 0 where
    no layer is windowed, ``fused_attention_layers`` where there is no
    attention (and on every CPU), ``remat_blocks`` where there are no
    blocks."""
    from distributed_learning_simulator_tpu.config import get_config
    from distributed_learning_simulator_tpu.simulator import run_simulation
    from distributed_learning_simulator_tpu.telemetry import spans

    argv = [
        "--dataset_name", "synthetic", "--model_name", "mlp",
        "--worker_number", "2", "--round", "1", "--epoch", "1",
        "--n_train", "64", "--n_test", "32", "--telemetry_level", "basic",
        "--log_root", str(tmp_path / "log"),
        "--compilation_cache_dir", "none",
    ]
    run_simulation(get_config(argv))
    counts = spans.last_run().counters()
    assert counts["attention_window"] == 0
    assert counts["swa_keys_per_query_block"] == 0
    assert counts["fused_attention_layers"] == 0
    assert counts["remat_blocks"] == 0  # no block is wrapped in nn.remat
