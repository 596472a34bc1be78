"""Jamba (models/jamba.py) and its selective scan (ops/selective_scan.py)
against the plain reference (benchmark/references/jamba.py) at a tiny
preset on the CPU: hidden 64, a period of 4 layers with attention at layer
1, Mamba mixers of 128 channels (or a rank's 32) with a state of 4 and a
step rank of 8, 4 query heads of 16 over one key/value head, an MLP of 96,
seeded weights, float32 products. The reference runs the scan position by
position and masks every key; the program runs the scan in chunks (the
XLA form here, the kernels in Pallas's interpreter) and attention in
blocks. Also: the share ties to the uncut layers, the tied head's
gradient, a FedAvg round through ``run_simulation``, the scopes and the
counters, and the shared code the other language models run."""

import functools
import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_learning_simulator_tpu.models import jamba as jm
from distributed_learning_simulator_tpu.models import lm_parts as parts
from distributed_learning_simulator_tpu.models.registry import (
    get_model,
    init_params,
)
from distributed_learning_simulator_tpu.models.traced_helpers import (
    causal_conv,
)
from distributed_learning_simulator_tpu.ops import selective_scan as ss

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HI = jax.lax.Precision.HIGHEST


def _module_at(*path):
    path = os.path.join(ROOT, *path) + ".py"
    spec = importlib.util.spec_from_file_location(
        "_t_jamba_" + os.path.basename(path)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _module_at("benchmark", "references", "jamba")

MODEL = {
    "hidden_size": 64, "num_hidden_layers": 4, "attn_layer_period": 4,
    "attn_layer_offset": 1, "num_attention_heads": 4,
    "num_key_value_heads": 1, "heads_held": 4, "mamba_expand": 2,
    "channels_held": 128, "mamba_d_state": 4, "mamba_d_conv": 4,
    "mamba_dt_rank": 8, "intermediate_size": 96, "mlp_columns_held": 96,
    "rms_norm_eps": 1e-6, "vocab_rows": 96,
}
#: One of four tensor-parallel ranks of MODEL's layers.
RANKS = 4
RANK_MODEL = {**MODEL, "heads_held": 1, "channels_held": 32,
              "mlp_columns_held": 24}
PRODUCTS = {
    "dense": lambda x, w: jnp.dot(x, w, precision=HI),
    "q": lambda a: a, "precision": HI,
}


def share_args(model=MODEL, **over):
    args = {k: v for k, v in model.items() if k != "vocab_rows"}
    return {**args, "dtype": "float32", **over}


def make_params(model=MODEL, seed=0):
    """Seeded weights from the reference's layout; the norms' scales and
    ``D`` are moved off 1 and the shifts and the convolution's bias off 0,
    so every parameter has a gradient that could be wrong."""
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


# --- the selective scan --------------------------------------------------------


def _scan_case(batch, length, channels, states, step, seed=0):
    """``x``, ``delta`` ``[batch, T, E]``, ``A`` ``[E, N]`` (negative),
    ``B``, ``C`` ``[batch, T, N]``, ``D`` ``[E]``; ``delta`` of about
    ``step`` a position."""
    rng = np.random.default_rng(seed)

    def draw(*s):
        return jnp.asarray(rng.standard_normal(s), jnp.float32)

    x = jax.nn.silu(draw(batch, length, channels))
    delta = step * jnp.exp(0.5 * draw(batch, length, channels))
    A = -jnp.exp(0.5 * draw(channels, states))
    return (x, delta, A, draw(batch, length, states),
            draw(batch, length, states), draw(channels))


def _with_chunk(chunk):
    """The XLA form at chunks of ``chunk`` positions."""
    return ss._with_rules(
        lambda *a: ss.xla_forward(*a, chunk=chunk),
        lambda *a: ss.xla_backward(*a, chunk=chunk))


@pytest.mark.parametrize("length,chunk,step", [
    (40, 8, 0.3),     # not a multiple of the chunk, five chunks
    (40, 8, 0.002),   # a step this small carries the state over every chunk
    (40, 16, 4.0),    # a state that forgets within a few positions
    (64, 16, 0.05),   # whole chunks
])
def test_xla_scan_is_the_recurrence(length, chunk, step):
    """The chunked XLA form against the reference's recurrence position
    by position, the output and all six gradients. Tolerances: f32 on
    both sides, the same products in the same order inside a position,
    the gradients by the explicit adjoint against autodiff's: 1e-5 of the
    largest output, 1e-4 of each gradient's largest."""
    args = _scan_case(2, length, 32, 4, step)
    scan = _with_chunk(chunk)
    out = scan(*args)
    assert out.shape == (2, length, 32)
    close(out, ref.scan(*args), 1e-5)
    tree_close(grads(scan, *args), grads(ref.scan, *args), 1e-4)


def test_a_small_step_carries_the_state_across_chunks():
    """With steps of 0.002 the state of the first chunk still reaches the
    last: the output there moves when the first position's input does
    (the chunk boundaries hand the state on)."""
    args = _scan_case(1, 40, 32, 4, 0.002)
    scan = _with_chunk(8)
    moved = args[0].at[:, 0].add(1.0)
    gap = jnp.abs(scan(moved, *args[1:]) - scan(*args))[:, 32:]
    assert float(jnp.min(jnp.max(gap, axis=-1))) > 1e-6


@pytest.mark.parametrize("batch,channels,block", [
    (1, 256, 128),   # two blocks of channels: dA, dB, dC, dD summed
    (2, 128, 640),   # two sequences, one block
])
def test_kernels_in_the_interpreter_are_the_recurrence(batch, channels,
                                                       block, monkeypatch):
    """The Pallas kernels in Pallas's interpreter, forward and backward,
    over two chunks of 128 positions (the backward walks them in
    reverse), against the recurrence: the output and all six gradients.
    Tolerances as the XLA form's."""
    monkeypatch.setattr(ss, "CHANNEL_BLOCK", block)
    args = _scan_case(batch, 2 * ss.CHUNK, channels, 16, 0.05, seed=4)
    assert ss.kernel_applies(2 * ss.CHUNK, channels)
    scan = ss._with_rules(
        functools.partial(ss.kernel_forward, interpret=True),
        functools.partial(ss.kernel_backward, interpret=True))
    close(scan(*args), ref.scan(*args), 1e-5)
    tree_close(grads(scan, *args), grads(ref.scan, *args), 1e-4)


def test_the_shapes_and_the_platform_choose_the_form():
    """Whole chunks over whole lane tiles lower to the kernels for a TPU
    and to the XLA form for the CPU; any other shape is the XLA form on
    both. The CPU's numbers are the XLA form's, digit for digit."""
    def loss(scan):
        return jax.jit(jax.grad(lambda *a: jnp.sum(scan(*a)),
                                argnums=tuple(range(6))))

    args = _scan_case(1, ss.CHUNK, 128, 16, 0.05)
    traced = loss(ss.selective_scan).trace(*args)
    assert "platform_index" in str(traced.jaxpr)
    tpu = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert tpu.count("tpu_custom_call") == 2  # forward and backward
    assert "tpu_custom_call" not in traced.lower(
        lowering_platforms=("cpu",)).as_text()
    for got, want in zip(loss(ss.selective_scan)(*args),
                         loss(ss.xla_scan)(*args)):
        assert jnp.array_equal(got, want)
    odd = _scan_case(1, 40, 96, 4, 0.05)
    assert not ss.kernel_applies(40, 128) and not ss.kernel_applies(128, 96)
    assert "platform_index" not in str(loss(ss.selective_scan).trace(
        *odd).jaxpr)


def test_channel_blocks_divide_the_channels(monkeypatch):
    assert ss.channel_block(1280) == 1280
    assert ss.channel_block(2560) == 1280
    assert ss.channel_block(384) == 384
    monkeypatch.setattr(ss, "CHANNEL_BLOCK", 640)
    assert ss.channel_block(1280) == 640
    assert ss.channel_block(384) == 384
    assert ss.channel_block(128) == 128


# --- layers and the model against the reference ------------------------------


LAYERS = {"mamba": ("layer_0", "mamba"), "attention": ("layer_1", "attn"),
          "mlp": ("layer_2", "mlp")}


def _program_layer(kind, p, x, c):
    if kind == "mamba":
        return jm.mamba(p, x, c)
    if kind == "attention":
        return jm.attn_full(p, x, c)
    return jm.mlp(p, x, dtype=jnp.float32)


def _reference_layer(kind, p, x, model=MODEL):
    if kind == "mamba":
        return ref.mamba(model, p, x, **PRODUCTS)
    if kind == "attention":
        return ref.attention(model, p, x, **PRODUCTS)
    return ref.mlp(p, x, PRODUCTS["dense"])


@pytest.mark.parametrize("length", [40, 128])
@pytest.mark.parametrize("kind", ["mamba", "attention", "mlp"])
def test_layer_matches_the_reference(kind, length):
    """Each layer, projections to output, and its gradients. Tolerances:
    f32 products at ``highest`` on both sides, the orders of the sums
    differ (chunks against steps, blocks of queries against one block):
    2e-4 of the largest output, 5e-4 of each gradient's largest."""
    layer, group = LAYERS[kind]
    p, x = make_params(seed=1)[layer][group], _inputs(length)
    c = jm.Share(**share_args())

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
    model = get_model("jamba", num_classes=96, vocab_rows=96, **share_args())

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
    (``weighted_nll``, the embedding as its kernel) against the
    reference's logits' loss, and its gradients (the same tolerances)."""
    from distributed_learning_simulator_tpu.parallel.engine import (
        make_loss_fn)

    params = make_params(seed=6)
    tokens = jnp.asarray(
        np.random.default_rng(6).integers(0, 96, (2, 40)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    model = get_model("jamba", num_classes=96, **share_args())

    def plain(params):
        return _nll(ref.forward(MODEL, params, tokens, **PRODUCTS), targets)

    got, got_grads = jax.value_and_grad(
        lambda p: make_loss_fn(model.apply)(
            p, tokens, targets, jnp.ones((2,)))[0])(params)
    want, want_grads = jax.value_and_grad(plain)(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    tree_close(got_grads, want_grads, 1e-3)


def test_the_tied_heads_gradient_is_the_lookups_and_the_heads():
    """The head's kernel is the embedding: the embedding's gradient is the
    sum of what the lookup gives it and what the head gives its kernel,
    each found by holding the other use of the table fixed (1e-5 of the
    largest: the same products, added in another order)."""
    from distributed_learning_simulator_tpu.parallel.engine import (
        make_loss_fn)

    params = make_params(seed=8)
    tokens = jnp.asarray(
        np.random.default_rng(8).integers(0, 96, (2, 40)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    model = get_model("jamba", num_classes=96, **share_args())
    loss = make_loss_fn(model.apply)

    def split(lookup, head):
        """The loss with the lookup reading ``lookup`` and the head
        ``head``: the model's own head, the table swapped in under it."""
        rest = {k: v for k, v in params.items() if k != "embed"}
        out = model.apply({"params": {**rest, "embed": {"table": lookup}}},
                          tokens)
        out.kernel = head.T
        return out.weighted_nll(targets, jnp.ones(targets.shape))[0] / (
            targets.size)

    table = params["embed"]["table"]
    whole = jax.grad(lambda p: loss(p, tokens, targets, jnp.ones((2,)))[0])(
        params)["embed"]["table"]
    by_lookup = jax.grad(lambda t: split(t, table))(table)
    by_head = jax.grad(lambda t: split(table, t))(table)
    assert float(jnp.max(jnp.abs(by_lookup))) > 0
    assert float(jnp.max(jnp.abs(by_head))) > 0
    close(whole, by_lookup + by_head, 1e-5)


def _rank_params(params, rank):
    """Rank ``rank`` of ``RANKS``'s share of an uncut layer's parameters:
    its channels, query heads and MLP columns, the k/v head and the inner
    norms whole."""
    E, H, F = (RANK_MODEL["channels_held"], RANK_MODEL["heads_held"],
               RANK_MODEL["mlp_columns_held"])
    hd = MODEL["hidden_size"] // MODEL["num_attention_heads"]
    c, h, f = (slice(rank * E, (rank + 1) * E),
               slice(rank * H * hd, (rank + 1) * H * hd),
               slice(rank * F, (rank + 1) * F))
    cols = {"in_x": c, "in_z": c, "conv": c, "dt_proj": c}
    rows = {"x_proj": c, "out": c, "conv_bias": c, "dt_bias_shift": c,
            "A_log_shift": c, "D": c}
    mamba = {k: (v[:, cols[k]] if k in cols else
                 v[rows[k]] if k in rows else v)
             for k, v in params["mamba"].items()}
    # ``b_dt`` is held as a shift from a start spread over the channels a
    # layer holds: the rank's shift is what gives the uncut layer's b_dt.
    whole = jm.dt_bias_start(MODEL["channels_held"])
    mamba["dt_bias_shift"] = mamba["dt_bias_shift"] + (
        whole[c] - jm.dt_bias_start(E))
    attn = {"q": params["attn"]["q"][:, h], "k": params["attn"]["k"],
            "v": params["attn"]["v"], "o": params["attn"]["o"][h]}
    mlp = {"gate": params["mlp"]["gate"][:, f], "up": params["mlp"]["up"][:, f],
           "down": params["mlp"]["down"][f]}
    return mamba, attn, mlp


def test_the_share_ties_to_the_uncut_layers():
    """model-configs § 4: four ranks' partials add up to the uncut
    reference's layers. Mamba: each rank's ``x W_xproj`` partial
    (:func:`jamba.mamba_in`), summed over the ranks where the deployment
    all-reduces it, feeds every rank's :func:`jamba.mamba_out`; the ranks'
    outputs summed are the uncut layer's (each rank's ``b_dt`` the uncut
    layer's on its channels). Attention and the MLP: the
    ranks' outputs summed (the one k/v head on every rank, counted once
    by each rank's own heads). The inner norms are whole on every rank.
    Tolerance: the same f32 products, summed in another order (1e-4)."""
    uncut = make_params(seed=11)
    x = _inputs(40, seed=12)
    c = jm.Share(**share_args(RANK_MODEL))
    layers = {"mamba": uncut["layer_0"]["mamba"],
              "attn": uncut["layer_1"]["attn"], "mlp": uncut["layer_0"]["mlp"]}
    ranks = [_rank_params(layers, r) for r in range(RANKS)]
    firsts = [jm.mamba_in(p, x, c) for p, _, _ in ranks]
    exchanged = sum(xdbc for _, _, xdbc in firsts)
    mamba = sum(jm.mamba_out(p, xr, zr, exchanged, c)
                for (p, _, _), (xr, zr, _) in zip(ranks, firsts))
    close(mamba, ref.mamba(MODEL, layers["mamba"], x, **PRODUCTS), 1e-4)
    attention = sum(jm.attn_full(a, x, c) for _, a, _ in ranks)
    close(attention, ref.attention(MODEL, layers["attn"], x, **PRODUCTS),
          1e-4)
    mlp = sum(jm.mlp(m, x, dtype=jnp.float32) for _, _, m in ranks)
    close(mlp, ref.mlp(layers["mlp"], x, PRODUCTS["dense"]), 1e-4)
    # The reference given one rank's share computes that rank's partial.
    one = ref.mamba(RANK_MODEL, ranks[1][0], x, **PRODUCTS)
    close(jm.mamba(ranks[1][0], x, c), one, 1e-4)


def _token_dataset(seed=0, n_train=2, n_test=2, length=48):
    from distributed_learning_simulator_tpu.data.registry import Dataset

    rng = np.random.default_rng(seed)
    x = rng.integers(0, 96, (n_train + n_test, length + 1)).astype(np.int32)
    return Dataset("tokens", x[:n_train, :-1], x[:n_train, 1:],
                   x[n_train:, :-1], x[n_train:, 1:], 96)


def test_one_in_flight_fedavg_round_is_the_references(tmp_path):
    """One FedAvg round through ``run_simulation`` under the benchmark's
    traffic flags (one client in flight, rounds not pipelined, the global
    donated), 2 clients of one sequence, one SGD step each: the new global
    model is the start less the rate times the mean of the clients'
    reference gradients, and the round's test loss is the reference's at
    that model. Tolerances: f32 on both sides, the change to 1e-3 of each
    tensor's largest change, the losses to 1e-5."""
    from distributed_learning_simulator_tpu.config import get_config
    from distributed_learning_simulator_tpu.simulator import run_simulation
    from distributed_learning_simulator_tpu.telemetry import spans

    argv = [
        "--dataset_name", "tokens", "--model_name", "jamba",
        "--model_args", json.dumps(share_args()),
        "--worker_number", "2", "--epoch", "1", "--batch_size", "1",
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
    model = get_model("jamba", num_classes=96, **share_args())
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
    assert counts["ssm_layers"] == 3
    assert counts["selective_scan_kernel"] == 0  # the CPU: the XLA form
    assert counts["fused_attention_layers"] == 0
    assert counts["remat_blocks"] == 0  # 16 GiB: every block keeps
    assert counts["head_backward_tied"] == 1
    assert counts["global_donated"] == 1
    assert counts["host_syncs"] == counts["rounds"] == 1


# --- scopes, counters, the share's arithmetic ---------------------------------


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def test_every_product_and_the_scan_are_traced_from_a_scopes_line():
    """The shared implementations (models/lm_parts.py,
    ops/selective_scan.py) are registered as not being user code, so every
    matrix product of the training loss and of its gradient carries a line
    of THIS model's scope functions, every scope has one, and the scan's
    kernels, lowered for a TPU, carry the line of ``ssm_scan``."""
    from jax._src import source_info_util

    from distributed_learning_simulator_tpu.parallel.engine import (
        make_loss_fn)

    def frames(model, tokens, primitive):
        params = jax.eval_shape(
            lambda: model.init(jax.random.key(0), tokens)["params"])
        train = jax.grad(lambda p: make_loss_fn(model.apply)(
            p, tokens, tokens, jnp.ones((tokens.shape[0],)))[0])
        for eqn in _equations(jax.make_jaxpr(train)(params).jaxpr):
            if eqn.primitive.name == primitive:
                yield source_info_util.user_frame(eqn.source_info.traceback)

    scopes = set()
    for frame in frames(get_model("jamba", num_classes=96, **share_args()),
                        jnp.zeros((2, 48), jnp.int32), "dot_general"):
        assert frame.file_name.endswith("models/jamba.py"), frame
        scope = jm.scope_of_line(frame.start_line)
        assert scope is not None, frame
        scopes.add(scope)
    assert scopes == set(jm._SCOPES) - {"ssm_scan"} == {
        "mamba", "attn_full", "mlp", "lm_head"}
    kernels = [jm.scope_of_line(f.start_line) for f in frames(
        get_model("jamba", num_classes=96, **share_args()),
        jnp.zeros((1, ss.CHUNK), jnp.int32), "pallas_call")]
    assert kernels == ["ssm_scan"] * 6  # 3 layers: forward, backward
    assert jm.scope_of_line(jm.mamba_out.__code__.co_firstlineno + 2) == (
        "mamba")
    assert jm.scope_of_line(jm.jamba.__code__.co_firstlineno) is None


@pytest.mark.parametrize("model,positions,layers,kernel,fused", [
    (lambda: jm.jamba(8192), 16384, 13, 1, 1),  # jamba2_fed_seq16k_c2
    (lambda: jm.jamba(96, **share_args()), 48, 3, 0, 0),
    (lambda: get_model("olmo_hybrid", num_classes=12544), 4096, 0, 0, 1),
    (lambda: get_model("resnet18", num_classes=10), 3072, 0, 0, 0),
], ids=["jamba_cell", "jamba_tiny", "olmo_cell", "image_cell"])
def test_counters_by_model(model, positions, layers, kernel, fused):
    """What ``run_simulation`` records as ``ssm_layers`` and asks for
    ``selective_scan_kernel`` and ``fused_attention_layers`` (reported on
    a TPU only): at the cell's 16,384 positions and 1,280 channels the
    scan takes its kernels, and heads of 2560 / 20 = 128 the fused
    attention; the tiny preset's shapes take neither."""
    m = model()
    assert getattr(m, "ssm_layers", 0) == layers
    assert getattr(m, "selective_scan_kernel", lambda _: 0)(
        positions) == kernel
    assert getattr(m, "fused_attention_layers", lambda _: 0)(
        positions) == fused


def test_the_cells_parameter_count():
    """The program's share of the cell holds 379,216,576 parameters, the
    configuration's count (benchmark/configs/jamba2_3b_l14_tp4_vp8.json):
    13 Mamba layers of 26,044,352, the attention layer's 19,665,920, the
    tied embedding's 20,971,520 and the final norm's 2,560."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "jamba2_3b_l14_tp4_vp8.json")) as f:
        config = json.load(f)
    model = config["model"]
    m = jm.jamba(config["data"]["vocab"], **model)
    shapes = jax.eval_shape(lambda: m.init(
        jax.random.key(0), jnp.zeros((1, ss.CHUNK), jnp.int32))["params"])

    def count(tree):
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(tree))

    assert count(shapes["layer_0"]) == config["parameters"]["mamba_layer"]
    assert count(shapes["layer_7"]) == (
        config["parameters"]["attention_layer"])
    assert count(shapes) == config["parameters"]["total"] == 379_216_576
    assert m.share.layer_kinds == (jm.MAMBA,) * 7 + (jm.ATTENTION,) + (
        jm.MAMBA,) * 6


def test_share_refuses_what_it_cannot_hold():
    with pytest.raises(ValueError, match="channels_held"):
        jm.jamba(96, channels_held=6000)
    with pytest.raises(ValueError, match="heads_held"):
        jm.jamba(96, heads_held=21)
    with pytest.raises(ValueError, match="whole groups"):
        jm.jamba(96, num_key_value_heads=4, heads_held=3)
    with pytest.raises(ValueError, match="head size"):
        jm.jamba(96, num_attention_heads=7, heads_held=7)
    model = jm.jamba(96, vocab_rows=64, **share_args())
    with pytest.raises(ValueError, match="vocab_rows"):
        model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))


def test_the_initial_steps_and_decays():
    """``A_log``'s start is ``log n`` for every channel and ``b_dt``'s
    makes steps of ``softplus(b_dt)`` spread log-uniformly over [0.001,
    0.1], the program's constants the reference's (written apart)."""
    steps = jax.nn.softplus(jm.dt_bias_start(1280))
    assert 0.001 < float(steps[0]) < float(steps[-1]) < 0.1
    ratios = steps[1:] / steps[:-1]
    close(ratios, jnp.full_like(ratios, 100 ** (1 / 1280)), 1e-4)
    close(jm.dt_bias_start(1280), ref.initial_dt_bias(1280), 1e-6)
    assert jnp.array_equal(jm.a_log_start(32, 16), ref.initial_a_log(32, 16))


# --- what the other language models keep --------------------------------------

#: sha256 of the lowered text (``jax.jit(...).lower(...).as_text()``, CPU,
#: this container's jax) of each shared part's output and gradient, called
#: as the other language models call it (2 sequences of 1,024 positions,
#: bfloat16 products): recorded at the commit (636dbd5) before this model
#: joined them.
PARENT_PARTS = {
    ("gated_attention", "solar_open2"):
        "a1ba32e6114431b1d0de977acfeaf602d85673533cab06838870275067f09f70",
    ("gated_attention", "afmoe"):
        "9578632ed4e2589599e17e01b5df650485618fa0f8d5ec784888a40fcfc1d5d0",
    ("gated_attention", "olmo_hybrid"):
        "5d11d0c99a849b81c988f082d5aff3ccc164276122344529c7393242d5930e3f",
    ("causal_conv", "delta_rule_models"):
        "bd6aac04fec4c5135bf332c268dc14c8e61cb5054c773dd0ab961b00c62350b8",
    ("head_nll", "every_language_model"):
        "4ce5065274a9a00ac1eb02a9939e941d1624087f6d56b5fe4ce18c77e779f047",
}


def _shared_call(part, model):
    """``(function of arrays, its arguments)``: the shared part as
    ``model`` calls it."""
    rng = np.random.default_rng(0)

    def draw(*s):
        return jnp.asarray(rng.standard_normal(s), jnp.float32)

    x = draw(2, 1024, 256)
    if part == "causal_conv":
        return causal_conv, (x, draw(4, 256))
    if part == "head_nll":
        targets = jnp.asarray(rng.integers(0, 96, (2, 1024)), jnp.int32)
        return (lambda k, x: parts.head_nll(
            k, x, targets, jnp.ones(targets.shape), dtype=jnp.bfloat16)[0]), (
                draw(256, 96), x)
    kw = {
        "solar_open2": dict(heads=2, kv_heads=1),
        "afmoe": dict(heads=2, kv_heads=1, qk_norm_eps=1e-5, window=256,
                      rope_theta=1e4),
        "olmo_hybrid": dict(heads=2, kv_heads=2, qk_norm_eps=1e-6,
                            qk_norm="projection"),
    }[model]
    hd, heads, kv = 128, kw["heads"], kw["kv_heads"]
    p = {"q": draw(256, heads * hd), "k": draw(256, kv * hd),
         "v": draw(256, kv * hd), "o": draw(heads * hd, 256)}
    if model != "olmo_hybrid":  # Solar's and Trinity's output gates
        p["g"] = draw(256, heads * hd)
    if model == "afmoe":
        p.update(q_norm=draw(hd), k_norm=draw(hd))
    if model == "olmo_hybrid":
        p.update(q_norm=draw(heads * hd), k_norm=draw(kv * hd))
    return (lambda p, x: parts.gated_attention(
        p, x, head_dim=hd, dtype=jnp.bfloat16, **kw)), (p, x)


def _lowered(part, model):
    fn, args = _shared_call(part, model)
    both = jax.value_and_grad(
        lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=tuple(range(len(args))))
    return hashlib.sha256(
        jax.jit(both).lower(*args).as_text().encode()).hexdigest()


@pytest.mark.parametrize("part,model", sorted(PARENT_PARTS))
def test_shared_parts_lower_to_the_parents_programs(part, model):
    """``gated_attention``, ``causal_conv`` and ``head_nll``, with their
    defaults and as Solar-Open2, Trinity-Mini and Olmo-Hybrid call them
    (the fused attention's shapes: heads of 128, whole blocks of 1,024),
    lower to the text they lowered to before this model shared them: the
    same program, so the same outputs and gradients bit for bit."""
    assert _lowered(part, model) == PARENT_PARTS[(part, model)]
