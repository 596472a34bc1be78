"""Olmo-Hybrid (models/olmo_hybrid.py) against its plain reference
(benchmark/references/olmo_hybrid.py) at a tiny preset on the CPU: hidden
64, a period ``[linear, linear, linear, full]``, Gated DeltaNet over 2 heads
with keys of 16 and values of 32, full attention over 2 heads of 32,
seeded weights, float32 products. The reference runs the delta rule
position by position and masks every key; the program runs the rule in
chunks and attention in blocks. Also: the shared delta rule's two decay
forms, and the programs of the other language models, which moved code
must leave as they were."""

import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_learning_simulator_tpu.models import lm_parts as parts
from distributed_learning_simulator_tpu.models import olmo_hybrid as oh
from distributed_learning_simulator_tpu.models.registry import (
    get_model,
    init_params,
)
from distributed_learning_simulator_tpu.models.traced_helpers import (
    l2_norm,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HI = jax.lax.Precision.HIGHEST


def _module_at(*path):
    path = os.path.join(ROOT, *path) + ".py"
    spec = importlib.util.spec_from_file_location(
        "_t_olmo_" + os.path.basename(path)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _module_at("benchmark", "references", "olmo_hybrid")

MODEL = {
    "hidden_size": 64, "num_hidden_layers": 4,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"],
    "num_attention_heads": 2, "num_key_value_heads": 2,
    "linear_num_key_heads": 2, "linear_num_value_heads": 2,
    "linear_key_head_dim": 16, "linear_value_head_dim": 32,
    "linear_conv_kernel_dim": 4, "intermediate_size": 96,
    "rms_norm_eps": 1e-6, "vocab_rows": 96,
}
PRODUCTS = {
    "dense": lambda x, w: jnp.dot(x, w, precision=HI),
    "q": lambda a: a, "precision": HI,
}


def share_args(model=MODEL, **over):
    args = {k: v for k, v in model.items() if k != "vocab_rows"}
    return {**args, "dtype": "float32", **over}


def share(model=MODEL, **over):
    args = share_args(model, **over)
    return oh.Share(**{**args, "layer_types": tuple(args["layer_types"])})


def make_params(model=MODEL, seed=0):
    """Seeded weights from the reference's layout; the norms' scales are
    moved off 1 and ``A_log``, ``dt_bias`` off 0, so every parameter has
    a gradient that could be wrong."""
    rng = np.random.default_rng(seed)
    tree = {}
    for path, (shape, kind) in sorted(ref.layout(model, None).items()):
        if kind == "ones":
            leaf = 1.0 + 0.1 * rng.standard_normal(shape)
        elif kind == "zeros":
            leaf = 0.3 * rng.standard_normal(shape)
        elif isinstance(kind, dict):
            leaf = kind["std"] * rng.standard_normal(shape)
        else:
            leaf = rng.standard_normal(shape) / np.sqrt(np.prod(shape[:-1]))
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


def grads(fn, *args):
    return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                    argnums=tuple(range(len(args))))(*args)


def _inputs(length, seed=1):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal((2, length, 64)),
        jnp.float32)


# --- the delta rule with one decay a head -----------------------------------


def _rule_case(length, decay, beta_bias=0.0, seed=0):
    """``q, k`` ``[2, T, 3, 16]``, ``v`` ``[2, T, 3, 32]``, one log decay
    and one beta a head and position."""
    rng = np.random.default_rng(seed)

    def draw(*s):
        return jnp.asarray(rng.standard_normal(s), jnp.float32)

    q = l2_norm(draw(2, length, 3, 16)) / 4.0
    k = l2_norm(draw(2, length, 3, 16))
    v = draw(2, length, 3, 32)
    g = -decay * jax.nn.softplus(draw(2, length, 3))
    beta = 2.0 * jax.nn.sigmoid(draw(2, length, 3) + beta_bias)
    return q, k, v, g, beta


@pytest.mark.parametrize("length,chunk,decay,beta_bias", [
    (100, 8, 0.05, 0.0),   # not a multiple of the chunk, a weak decay
    (100, 64, 1.0, 0.0),   # the model's chunk, two of them, padded
    (64, 16, 8.0, 0.0),    # a decay of 8 a position: underflow, no overflow
    (40, 8, 0.3, 6.0),     # beta near 2: I - b k k^T near a reflection
])
def test_scalar_decay_rule_is_the_recurrence(length, chunk, decay,
                                             beta_bias):
    """The chunked rule with ONE decay a head (keys 16 wide, values 32:
    a state that is not square) against the recurrence position by
    position, outputs and every gradient. Tolerances: the chunk's
    triangular solve and the state's products in f32 against the
    reference's steps in f32, different orders of the same sums (1e-5 of
    the largest output and 1e-4 of each gradient's, as the per-channel
    form's test holds)."""
    args = _rule_case(length, decay, beta_bias)
    assert float(jnp.max(args[4])) > 1.99 or beta_bias == 0.0

    def plain(*a):
        return ref.delta_rule(*a, HI)

    def chunked(*a):
        return parts.chunked_delta_rule(*a, jnp.float32, chunk=chunk)

    out = chunked(*args)
    assert out.shape == (2, length, 3, 32)
    close(out, plain(*args), 1e-5)
    tree_close(grads(chunked, *args), grads(plain, *args), 1e-4)


def test_the_decay_shape_chooses_the_form():
    """``[B, T, H]`` and ``[B, T, H, 1]`` take the scalar form, with no
    ``[sub, sub, d]`` decay factors in the program; ``[B, T, H, d]`` the
    per-channel form."""
    q, k, v, g, beta = _rule_case(64, 1.0)

    def rule(decay):
        return parts.chunked_delta_rule(q, k, v, decay, beta, jnp.float32)

    def text(decay):
        return str(jax.make_jaxpr(rule)(decay))

    for scalar in (g, g[..., None]):
        assert "16,16,16]" not in text(scalar)  # [.., sub, sub, d]: never
    assert "16,16,16]" in text(jnp.broadcast_to(g[..., None], q.shape))
    assert jnp.array_equal(rule(g), rule(g[..., None]))


@pytest.mark.parametrize("length", [64, 100])
def test_scalar_form_is_the_per_channel_form_broadcast(length):
    """One decay a head is a decay per key channel that is the same in
    every channel: both forms of the rule agree, outputs and gradients
    (the per-channel form's gradient of the decay summed over the
    channels). Tolerance as above: the two forms sum in different
    orders."""
    q, k, v, g, beta = _rule_case(length, 1.0, seed=3)

    def scalar(q, k, v, g, beta):
        return parts.chunked_delta_rule(q, k, v, g, beta, jnp.float32)

    def channels(q, k, v, g, beta):
        wide = jnp.broadcast_to(g[..., None], q.shape)
        return parts.chunked_delta_rule(q, k, v, wide, beta, jnp.float32)

    close(scalar(q, k, v, g, beta), channels(q, k, v, g, beta), 1e-5)
    tree_close(grads(scalar, q, k, v, g, beta),
               grads(channels, q, k, v, g, beta), 1e-4)


# --- layers and the model against the reference ------------------------------


LAYERS = {"gdn": ("layer_1", "gdn"), "full": ("layer_3", "attn"),
          "mlp": ("layer_0", "mlp")}


def _program_layer(kind, p, x, c):
    if kind == "gdn":
        return oh.gdn(p, x, c)
    if kind == "full":
        return oh.attn_full(p, x, c)
    return oh.mlp(p, x, dtype=jnp.float32)


def _reference_layer(kind, p, x, model=MODEL):
    if kind == "gdn":
        return ref.gdn(model, p, x, **PRODUCTS)
    if kind == "full":
        return ref.attention(model, p, x, **PRODUCTS)
    return ref.mlp(p, x, PRODUCTS["dense"])


@pytest.mark.parametrize("length", [80, 128])
@pytest.mark.parametrize("kind", ["gdn", "full", "mlp"])
def test_layer_matches_the_reference(kind, length):
    """Each layer, projections to output, and its gradients. Tolerances:
    f32 products at ``highest`` on both sides, the orders of the sums
    differ (chunks against steps, blocks of queries against one block):
    2e-4 of the largest output, 5e-4 of each gradient's largest."""
    layer, group = LAYERS[kind]
    p, x, c = make_params(seed=1)[layer][group], _inputs(length), share()

    def program(p, x):
        return _program_layer(kind, p, x, c)

    def plain(p, x):
        return _reference_layer(kind, p, x)

    close(program(p, x), plain(p, x))
    tree_close(grads(program, p, x), grads(plain, p, x), 5e-4)


def _nll(logits, targets):
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


def test_whole_model_loss_and_gradients():
    """The logits' loss and every parameter's gradient (1e-4 of the loss,
    1e-3 of each gradient's largest: four layers of the differences
    above); the flax module declares exactly the reference's layout."""
    params = make_params(seed=3)
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, 96, (2, 72)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    model = get_model("olmo_hybrid", num_classes=96, vocab_rows=96,
                      **share_args())

    def program(params):
        return _nll(model.apply({"params": params}, tokens).logits(),
                    targets)

    def plain(params):
        return _nll(ref.forward(MODEL, params, tokens, **PRODUCTS), targets)

    got, got_grads = jax.value_and_grad(program)(params)
    want, want_grads = jax.value_and_grad(plain)(params)
    assert abs(float(got) - float(want)) < 1e-4 * float(want)
    tree_close(got_grads, want_grads, 1e-3)
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
    (``weighted_nll``) against the reference's logits' loss, and its
    gradients (the same tolerances)."""
    from distributed_learning_simulator_tpu.parallel.engine import (
        make_loss_fn)

    params = make_params(seed=6)
    tokens = jnp.asarray(
        np.random.default_rng(6).integers(0, 96, (2, 40)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    model = get_model("olmo_hybrid", num_classes=96, **share_args())

    def plain(params):
        return _nll(ref.forward(MODEL, params, tokens, **PRODUCTS), targets)

    got, got_grads = jax.value_and_grad(
        lambda p: make_loss_fn(model.apply)(
            p, tokens, targets, jnp.ones((2,)))[0])(params)
    want, want_grads = jax.value_and_grad(plain)(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    tree_close(got_grads, want_grads, 1e-3)


def test_the_vocabulary_share_is_the_uncut_models_rows():
    """model-configs § 4: the chip that holds rows ``[24, 48)`` of a
    vocabulary of 96, given ids of that slice, gives the logits of the
    uncut model's columns ``[24, 48)`` for the same ids, and what the
    other three slices give makes up the rest of the uncut logits (the
    embedding and the head are vocabulary-parallel; the layers are
    whole)."""
    params = make_params(seed=9)
    tokens = jnp.asarray(
        np.random.default_rng(9).integers(0, 24, (2, 40)), jnp.int32)
    model = get_model("olmo_hybrid", num_classes=24, vocab_rows=24,
                      **share_args())
    pieces = []
    for first in range(0, 96, 24):
        mine = {**params,
                "embed": {"table": params["embed"]["table"][
                    first:first + 24]},
                "lm_head": {"kernel": params["lm_head"]["kernel"][
                    :, first:first + 24]}}
        got = model.apply({"params": mine}, tokens).logits()
        want = ref.forward(MODEL, params, tokens + first, **PRODUCTS)
        close(got, want[..., first:first + 24])
        pieces.append(ref.forward(
            {**MODEL, "vocab_rows": 24}, mine, tokens, **PRODUCTS))
    whole = ref.forward(MODEL, params, tokens + 24, **PRODUCTS)
    assert whole.shape[-1] == sum(p.shape[-1] for p in pieces)
    close(pieces[1], whole[..., 24:48])


def _token_dataset(seed=0, n_train=4, n_test=2, length=80):
    from distributed_learning_simulator_tpu.data.registry import Dataset

    rng = np.random.default_rng(seed)
    x = rng.integers(0, 96, (n_train + n_test, length + 1)).astype(np.int32)
    return Dataset("tokens", x[:n_train, :-1], x[:n_train, 1:],
                   x[n_train:, :-1], x[n_train:, 1:], 96)


def test_one_in_flight_fedavg_round_is_the_references(tmp_path):
    """One FedAvg round through ``run_simulation`` under the benchmark's
    traffic flags (one client in flight, rounds not pipelined, the global
    donated), 4 clients of one sequence, one SGD step each: the new global
    model is the start less the rate times the mean of the clients'
    reference gradients, and the round's test loss is the reference's at
    that model. Tolerances: f32 on both sides, the change to 1e-3 of each
    tensor's largest change, the losses to 1e-5."""
    from distributed_learning_simulator_tpu.config import get_config
    from distributed_learning_simulator_tpu.simulator import run_simulation
    from distributed_learning_simulator_tpu.telemetry import spans

    argv = [
        "--dataset_name", "tokens", "--model_name", "olmo_hybrid",
        "--model_args", json.dumps(share_args()),
        "--worker_number", "4", "--epoch", "1", "--batch_size", "1",
        "--round", "1", "--client_chunk_size", "1",
        "--pipeline_rounds", "false", "--participation_fraction", "1.0",
        "--partition", "iid", "--client_residency", "resident",
        "--eval_batch_size", "1", "--optimizer_name", "sgd",
        "--learning_rate", "0.3", "--momentum", "0", "--seed", "5",
        "--distributed_algorithm", "fed", "--telemetry_level", "basic",
        "--log_root", str(tmp_path / "log"),
        "--compilation_cache_dir", "none",
    ]
    config = get_config(argv)
    data = _token_dataset()
    model = get_model("olmo_hybrid", num_classes=96, **share_args())
    start = init_params(model, data.x_train[:1], seed=config.seed)
    result = run_simulation(config, dataset=data)

    def loss(p, x, y):
        return _nll(ref.forward(MODEL, p, x[None], **PRODUCTS), y[None])

    mean_grad = jax.tree_util.tree_map(
        lambda *g: sum(g) / len(g),
        *(jax.grad(loss)(start, x, y)
          for x, y in zip(data.x_train, data.y_train)))
    want = jax.tree_util.tree_map(lambda g: -0.3 * g, mean_grad)
    got = jax.tree_util.tree_map(
        lambda a, b: a - b, result["global_params"], start)
    tree_close(got, want, 1e-3)
    new = jax.tree_util.tree_map(lambda a, b: a + b, start, want)
    test_loss = np.mean([float(loss(new, x, y))
                         for x, y in zip(data.x_test, data.y_test)])
    assert result["history"][0]["test_loss"] == pytest.approx(
        test_loss, rel=1e-5)
    counts = spans.last_run().counters()
    assert counts["delta_rule_decay_width"] == 1
    assert counts["fused_attention_layers"] == 0  # the CPU: the XLA form
    assert counts["remat_blocks"] == 0  # 16 GiB: every block keeps
    assert counts["head_backward_tied"] == 1
    assert counts["client_axis_width"] == 1
    assert counts["global_donated"] == 1
    assert counts["host_syncs"] == counts["rounds"] == 1
    assert "expert_load" not in result["history"][0]  # a dense model


# --- scopes and counters ------------------------------------------------------


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def test_every_product_is_traced_from_the_line_of_a_scope():
    """The shared implementations (models/lm_parts.py, the delta rule
    among them) are registered as not being user code, so every matrix
    product of the training loss and of its gradient carries a line of
    THIS model's scope functions, and every scope has one."""
    from jax._src import source_info_util

    from distributed_learning_simulator_tpu.parallel.engine import (
        make_loss_fn)

    model = get_model("olmo_hybrid", num_classes=96, **share_args())
    params = make_params()
    tokens = jnp.zeros((2, 48), jnp.int32)
    train = jax.grad(lambda p: make_loss_fn(model.apply)(
        p, tokens, tokens, jnp.ones((2,)))[0])
    scopes = set()
    for eqn in _equations(jax.make_jaxpr(train)(params).jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        frame = source_info_util.user_frame(eqn.source_info.traceback)
        assert frame.file_name.endswith("models/olmo_hybrid.py"), frame
        scope = oh.scope_of_line(frame.start_line)
        assert scope is not None, frame
        scopes.add(scope)
    assert scopes == set(oh._SCOPES) == {"gdn", "attn_full", "mlp",
                                         "lm_head"}
    assert oh.scope_of_line(oh.gdn.__code__.co_firstlineno + 2) == "gdn"
    assert oh.scope_of_line(oh.olmo_hybrid.__code__.co_firstlineno) is None


@pytest.mark.parametrize("model,positions,width,fused", [
    (lambda: oh.olmo_hybrid(12544), 4096, 1, 1),   # olmo_hybrid_fed_seq4k_c4
    (lambda: get_model("solar_open2", num_classes=24576), 4096, 128, 1),
    (lambda: get_model("afmoe", num_classes=25024), 8192, 0, 5),
    (lambda: get_model("resnet18", num_classes=10), 3072, 0, 0),
    (lambda: oh.olmo_hybrid(12544, layer_types=["full_attention"] * 4),
     4096, 0, 4),
], ids=["olmo_cell", "solar_cell", "trinity_cell", "image_cell",
        "no_linear_layer"])
def test_counters_by_model(model, positions, width, fused):
    """What ``run_simulation`` records as ``delta_rule_decay_width`` (1:
    one decay a head; 128: one a key channel; 0: no such layer) and asks
    for ``fused_attention_layers`` (reported on a TPU only): at the cell's
    4,096 positions and heads of 3840 / 30 = 128 the full layer takes the
    kernel."""
    m = model()
    assert getattr(m, "delta_rule_decay_width", 0) == width
    assert getattr(m, "fused_attention_layers", lambda _: 0)(
        positions) == fused


def test_share_refuses_what_it_cannot_hold():
    with pytest.raises(ValueError, match="layer_types"):
        oh.olmo_hybrid(96, layer_types=["linear_attention"])
    with pytest.raises(ValueError, match="value heads"):
        oh.olmo_hybrid(96, linear_num_value_heads=15)
    with pytest.raises(ValueError, match="head size"):
        oh.olmo_hybrid(96, num_attention_heads=7)
    model = oh.olmo_hybrid(96, vocab_rows=64, **share_args())
    with pytest.raises(ValueError, match="vocab_rows"):
        model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))


# --- what the other language models keep --------------------------------------

#: sha256 of the lowered text (``jax.jit(...).lower(...).as_text()``, CPU,
#: this container's jax) of the engine's loss and its gradient for the
#: language models at their tests' tiny presets, 2 sequences of 80
#: positions, every block rematerialised: the other two models' at the
#: commit (e46d60f) before the delta rule moved to models/lm_parts.py and
#: ``gated_attention`` took an optional gate and a norm over the whole
#: projection, this model's at the commit (967e0c6) before the blocks
#: could keep their residuals.
PARENT_LOWERED = {
    ("olmo_hybrid", "float32"):
        "6b8a5b4b21dc682cf60e8b66cdc42e0b09ef65fc34da2c4e31def3fe082cad1e",
    ("olmo_hybrid", "bfloat16"):
        "adae48d593bf064d8f381034833555219d92e5893e136135285e4cee8e19b0d9",
    ("solar_open2", "float32"):
        "74bfe690862036567f24c4ca58dfb932952282c24a8c89374baefb55abfb6e3c",
    ("solar_open2", "bfloat16"):
        "bae178a268c8ab20dcd7a57817ba53a96cd7079193399a211f39953cde3f7957",
    ("afmoe", "float32"):
        "ac0ff96f7676026a20bca30970fb16240c7bf8ec68bcc99fc78de72a2ad96dee",
    ("afmoe", "bfloat16"):
        "086b984ea0c52e1798d6d037133b8c2e533ea801fe153d80a73c4dd84e5c5877",
}
_PRESETS = {
    "solar_open2": dict(
        hidden_size=64, num_hidden_layers=4, gqa_layers=[0], head_dim=16,
        num_attention_heads=8, num_key_value_heads=4, heads_held=4,
        n_routed_experts=8, experts_held=4, num_experts_per_tok=2,
        moe_intermediate_size=32, gate_rank=8),
    "afmoe": dict(
        hidden_size=64, num_hidden_layers=5, num_dense_layers=1,
        layer_types=["sliding_attention"] * 4 + ["full_attention"],
        head_dim=16, num_attention_heads=8, num_key_value_heads=2,
        sliding_window=32, intermediate_size=96, num_experts=16,
        experts_held=4, num_experts_per_tok=2, moe_intermediate_size=32,
        query_block=16),
    "olmo_hybrid": {k: v for k, v in share_args().items() if k != "dtype"},
}


@pytest.mark.parametrize("name,dtype", sorted(PARENT_LOWERED))
def test_the_other_models_lower_to_the_parents_programs(name, dtype,
                                                        monkeypatch):
    """With no room on the device every block is rematerialised
    (models/lm_parts.py ``rematerialised_blocks``), and each model lowers,
    loss and gradient, to the text it lowered to when every block always
    was: Solar-Open2's KDA through the moved rule (a decay per key
    channel), both other models' attention through ``gated_attention``'s
    defaults (the gate, the per-head norm). The same program, so the same
    numbers digit for digit (a line number is not in the text)."""
    monkeypatch.setattr(parts, "device_memory_limit", lambda: (0, "none"))
    model = get_model(name, num_classes=96, **_PRESETS[name], dtype=dtype)
    tokens = jnp.zeros((2, 80), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), tokens)["params"])
    from distributed_learning_simulator_tpu.parallel.engine import (
        make_loss_fn)

    loss = make_loss_fn(model.apply)
    fn = jax.value_and_grad(lambda p, x: loss(p, x, x, jnp.ones((2,)))[0])
    text = jax.jit(fn).lower(params, tokens).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        PARENT_LOWERED[(name, dtype)])
