"""Solar-Open2 (models/solar_open2.py) against its plain reference
(benchmark/references/solar_open2.py) at a tiny preset on the CPU: hidden
64, 4 heads of 16 held of 8, 8 experts top-2 of which 4 are held, T 48-100,
seeded weights, float32 products. The reference is the architecture
position by position; the program chunks the delta rule, groups the
routed tokens by expert and batches the sequences."""

import functools
import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_learning_simulator_tpu.models import solar_open2 as so
from distributed_learning_simulator_tpu.models.registry import get_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HI = jax.lax.Precision.HIGHEST


def _module_at(*parts):
    path = os.path.join(ROOT, *parts) + ".py"
    spec = importlib.util.spec_from_file_location(
        "_t_" + "_".join(parts), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _module_at("benchmark", "references", "solar_open2")

MODEL = {
    "hidden_size": 64, "num_hidden_layers": 4, "gqa_layers": [0],
    "head_dim": 16, "num_attention_heads": 8, "num_key_value_heads": 4,
    "heads_held": 4, "n_routed_experts": 8, "experts_held": 4,
    "expert_offset": 0, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "vocab_rows": 96,
    "short_conv_kernel_size": 4, "gate_rank": 8, "rms_norm_eps": 1e-5,
}
PRODUCTS = {
    "dense": lambda x, w: jnp.dot(x, w, precision=HI),
    "q": lambda a: a, "precision": HI,
}


def share_args(model=MODEL, **over):
    args = {k: v for k, v in model.items() if k != "vocab_rows"}
    return {**args, "dtype": "float32", **over}


def make_params(model=MODEL, seed=0):
    """Seeded weights from the reference's layout; ``A_log`` and
    ``dt_bias`` are moved off their constant start so every parameter
    has a gradient that could be wrong."""
    rng = np.random.default_rng(seed)
    tree = {}
    for path, (shape, kind) in sorted(ref.layout(model, None).items()):
        if kind == "ones":
            leaf = 1.0 + 0.1 * rng.standard_normal(shape)
        elif kind == "zeros":
            leaf = 0.3 * rng.standard_normal(shape)
        else:
            fan_in = (
                kind["fan_in"] if isinstance(kind, dict) and "fan_in" in kind
                else 1.0 / kind["std"] ** 2 if isinstance(kind, dict)
                else int(np.prod(shape[:-1]))
            )
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


@pytest.mark.parametrize("decay", [0.05, 1.0, 8.0])
@pytest.mark.parametrize("length", [64, 100, 192])
def test_chunked_delta_rule_is_the_recurrence(length, decay):
    """Chunks of 64 with the UT transform against the recurrence position
    by position, outputs and every gradient; 100 is not a multiple of the
    chunk; a decay of 8 a position underflows what it multiplies and must
    overflow nothing."""
    rng = np.random.default_rng(length)
    shape = (2, length, 3, 16)

    def draw(*s):
        return jnp.asarray(rng.standard_normal(s), jnp.float32)

    q = so._l2_norm(draw(*shape)) / 4.0
    k = so._l2_norm(draw(*shape))
    v = draw(*shape)
    g = -decay * jax.nn.softplus(draw(*shape))
    beta = 2.0 * jax.nn.sigmoid(draw(*shape[:3]))
    args = (q, k, v, g, beta)

    def plain(*a):
        return ref.delta_rule(*a, HI)

    def chunked(*a):
        return so.chunked_delta_rule(*a, jnp.float32)

    close(chunked(*args), plain(*args), 1e-5)

    def grads(fn):
        return jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2, 3, 4)
        )(*args)

    tree_close(grads(chunked), grads(plain), 1e-4)


def _layer_case(kind, length=80, seed=1):
    params = make_params(seed=seed)
    layer = {"gqa": "layer_0", "kda": "layer_1", "moe": "layer_2"}[kind]
    x = jnp.asarray(
        np.random.default_rng(seed).standard_normal((2, length, 64)),
        jnp.float32,
    )
    return params[layer][kind], x


def _program_layer(kind, p, x, **over):
    c = so.Share(**share_args(**over))
    if kind == "kda":
        return so.kda_mixer(p, x, heads=c.heads_held, head_dim=c.head_dim,
                            eps=c.rms_norm_eps, dtype=jnp.float32)
    if kind == "gqa":
        return so.gqa_mixer(p, x, heads=c.heads_held,
                            kv_heads=c.kv_heads_held, head_dim=c.head_dim,
                            dtype=jnp.float32)
    tokens = x.reshape(-1, x.shape[-1])
    combine = so.moe_route(
        p, tokens, top_k=c.num_experts_per_tok,
        expert_offset=c.expert_offset, experts_held=c.experts_held,
    )
    routed, load, overflow = so.moe_experts(
        p, tokens, combine, capacity=c.expert_capacity(tokens.shape[0]),
        dtype=jnp.float32,
    )
    y = routed + so.moe_shared(p, tokens, dtype=jnp.float32)
    return y.reshape(x.shape), load, overflow


@pytest.mark.parametrize("kind", ["kda", "gqa", "moe"])
def test_layer_matches_the_reference(kind):
    p, x = _layer_case(kind)

    def program(p, x):
        out = _program_layer(kind, p, x)
        return out[0] if kind == "moe" else out

    def plain(p, x):
        return getattr(ref, kind)(MODEL, p, x, **PRODUCTS)

    close(program(p, x), plain(p, x))

    def grads(fn):
        return jax.grad(lambda p, x: jnp.sum(jnp.sin(fn(p, x))),
                        argnums=(0, 1))(p, x)

    tree_close(grads(program), grads(plain), 5e-4)


def test_attention_in_query_blocks_is_attention():
    """Scores materialised a block of queries at a time (what the cell's
    4096 positions run) are the scores of the whole sequence."""
    p, x = _layer_case("gqa", length=96)
    kw = dict(heads=4, kv_heads=2, head_dim=16, dtype=jnp.float32)
    want = ref.gqa(MODEL, p, x, **PRODUCTS)
    for block in (16, 96):
        close(so.gqa_mixer(p, x, query_block=block, **kw), want)

    def grads(fn):
        return jax.grad(lambda p, x: jnp.sum(jnp.sin(fn(p, x))),
                        argnums=(0, 1))(p, x)

    tree_close(
        grads(lambda p, x: so.gqa_mixer(p, x, query_block=16, **kw)),
        grads(lambda p, x: ref.gqa(MODEL, p, x, **PRODUCTS)), 5e-4,
    )


@pytest.mark.parametrize("length,block", [(96, 40), (70, 32)])
def test_attention_pads_its_last_block_of_queries(length, block):
    """A length that is not a multiple of the query block still runs a
    block at a time, the last block padded with queries whose rows are
    dropped; it used to fall back, silently, to ONE block of every query
    (at 8,192 positions and 32 heads: 8.6 GB of scores)."""
    p, x = _layer_case("gqa", length=length)
    kw = dict(heads=4, kv_heads=2, head_dim=16, dtype=jnp.float32,
              query_block=block)
    close(so.gqa_mixer(p, x, **kw), ref.gqa(MODEL, p, x, **PRODUCTS))
    text = str(jax.make_jaxpr(lambda p, x: so.gqa_mixer(p, x, **kw))(p, x))
    assert f"{block},{length}]" in text  # scores: a block against the keys
    assert f"{length},{length}]" not in text

    def grads(fn):
        return jax.grad(lambda p, x: jnp.sum(jnp.sin(fn(p, x))),
                        argnums=(0, 1))(p, x)

    tree_close(
        grads(lambda p, x: so.gqa_mixer(p, x, **kw)),
        grads(lambda p, x: ref.gqa(MODEL, p, x, **PRODUCTS)), 5e-4,
    )


@pytest.mark.parametrize("factor,overflows", [(1.5, False), (0.25, True)])
def test_no_token_is_dropped_whatever_the_capacity(
        factor, overflows, monkeypatch):
    """Grouped by expert into slots where they fit; every token through
    every held expert where one expert is chosen by more than its slots.
    Either way the reference's result, and the load counts every
    assignment."""
    p, x = _layer_case("moe", length=96)
    monkeypatch.setattr(so.parts, "EXPERT_CAPACITY_FACTOR", factor)
    c = so.Share(**share_args())
    assert c.expert_capacity(2 * 96) < 2 * 96  # the grouped path is there
    y, load, overflow = _program_layer("moe", p, x)
    close(y, ref.moe(MODEL, p, x, **PRODUCTS))
    assert bool(overflow) is overflows
    index, _ = ref.route(MODEL, p, x, PRODUCTS["dense"])
    want = [int(jnp.sum(index == e)) for e in range(4)]
    assert load.tolist() == want


def test_whole_model_loss_and_gradients():
    params = make_params(seed=3)
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, 96, (2, 72)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    model = get_model("solar_open2", num_classes=96, vocab_rows=96,
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
    assert counts["moe_routed_tokens"].tolist() == [144] * 4
    assert int(jnp.sum(counts["moe_expert_load"])) == int(
        jnp.sum(counts["moe_local_assignments"]))
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


WHOLE = {**MODEL, "heads_held": 8, "experts_held": 8}


def test_the_shares_add_up_to_the_uncut_layer():
    """model-configs § 4: over all shares of the experts, and of the
    heads, the parts of a layer's result add up, with what every chip
    computes alike (the shared expert) counted once, to what the uncut
    reference gives for the whole layer."""
    whole = make_params(WHOLE, seed=5)
    x = jnp.asarray(
        np.random.default_rng(5).standard_normal((2, 56, 64)), jnp.float32)

    # Experts: 8 in shares of 4 and of 2.
    p = whole["layer_2"]["moe"]
    want = ref.moe(WHOLE, p, x, **PRODUCTS)
    only_shared = ref.moe({**WHOLE, "experts_held": 0}, p, x, **PRODUCTS)
    for held in (4, 2):
        total = 0.0
        for offset in range(0, 8, held):
            mine = {
                **p, **{k: p[k][offset:offset + held]
                        for k in ("gate", "up", "down")},
            }
            y, load, _ = _program_layer(
                "moe", mine, x, experts_held=held, expert_offset=offset)
            total = total + y
        shares = 8 // held
        close(total - (shares - 1) * only_shared, want)

    # Heads: 8 in shares of 4 and of 2 (whole groups of 2 query heads a
    # key/value head): columns of the projections in, rows of the one out.
    def columns(a, lo, hi, width=16):
        return a[:, lo * width:hi * width]

    for kind, layer in (("gqa", "layer_0"), ("kda", "layer_1")):
        p = whole[layer][kind]
        want = getattr(ref, kind)(WHOLE, p, x, **PRODUCTS)
        for held in (4, 2):
            total = 0.0
            for lo in range(0, 8, held):
                hi = lo + held
                mine = dict(p)
                if kind == "gqa":
                    for name in ("q", "g"):
                        mine[name] = columns(p[name], lo, hi)
                    for name in ("k", "v"):
                        mine[name] = columns(p[name], lo // 2, hi // 2)
                else:
                    for name in ("q", "k", "v", "conv_q", "conv_k",
                                 "conv_v", "f_b", "g_b"):
                        mine[name] = columns(p[name], lo, hi)
                    mine["dt_bias"] = p["dt_bias"][lo * 16:hi * 16]
                    mine["A_log"] = p["A_log"][lo:hi]
                    mine["b"] = p["b"][:, lo:hi]
                mine["o"] = p["o"][lo * 16:hi * 16]
                total = total + _program_layer(
                    kind, mine, x, heads_held=held)
            close(total, want)


def test_a_line_of_the_model_file_names_its_scope():
    line = so.kda_mixer.__code__.co_firstlineno + 3
    assert so.scope_of_line(line) == "kda"
    assert so.scope_of_line(so.moe_experts.__code__.co_firstlineno + 1) == (
        "moe/experts")
    assert so.scope_of_line(so.solar_open2.__code__.co_firstlineno) is None
    assert set(so._SCOPES) == {
        "kda", "gqa", "moe/route", "moe/experts", "moe/shared", "lm_head"}


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def test_every_product_is_traced_from_the_line_of_a_scope():
    """A device trace names an op by the innermost frame of user code:
    the shared helpers (models/traced_helpers.py) are registered as not
    being user code, so every matrix product of the loss and of its
    gradient carries a line of the mixer or expert layer that made it."""
    from jax._src import source_info_util

    model = get_model("solar_open2", num_classes=96, **share_args())
    params = make_params()
    tokens = jnp.zeros((2, 48), jnp.int32)

    def loss(p):
        head, _ = model.apply({"params": p}, tokens)
        return jnp.sum(head.logits())

    def scopes_of_products(jaxpr):
        scopes = set()
        for eqn in _equations(jaxpr):
            if eqn.primitive.name != "dot_general":
                continue
            frame = source_info_util.user_frame(eqn.source_info.traceback)
            assert frame.file_name.endswith("models/solar_open2.py"), frame
            scope = so.scope_of_line(frame.start_line)
            assert scope is not None, frame
            scopes.add(scope)
        return scopes

    jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    assert scopes_of_products(jaxpr) == set(so._SCOPES)

    # The training path: the head makes its loss and its gradients in a
    # rule of its own (head_nll), and EVERY op of that rule, not only its
    # products, is traced from a line of scope ``lm_head``.
    from distributed_learning_simulator_tpu.parallel.engine import (
        make_loss_fn)

    train = jax.grad(lambda p: make_loss_fn(model.apply)(
        p, tokens, tokens, jnp.ones((2,)))[0])
    jaxpr = jax.make_jaxpr(train)(params).jaxpr
    assert scopes_of_products(jaxpr) == set(so._SCOPES)
    rule = [
        eqn for eqn in _equations(jaxpr)
        if any(getattr(v.aval, "shape", ())[-1:] == (96,)
               and len(v.aval.shape) == 3 for v in eqn.outvars)
    ]  # whatever is of vocabulary width: [2, 48, 96]
    assert len(rule) > 8
    for eqn in rule:
        frame = source_info_util.user_frame(eqn.source_info.traceback)
        assert frame.file_name.endswith("models/solar_open2.py"), frame
        assert so.scope_of_line(frame.start_line) == "lm_head", frame


def _head_case(dtype, kernel_dtype, length, seed=11):
    rng = np.random.default_rng(seed)
    kernel = jnp.asarray(rng.standard_normal((64, 96)) / 8, kernel_dtype)
    x = jnp.asarray(rng.standard_normal((2, length, 64)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 96, (2, length)), jnp.int32)
    return kernel, x, y, jnp.dtype(dtype)


@pytest.mark.parametrize("dtype,kernel_dtype,length", [
    ("float32", "float32", 16), ("float32", "float32", 9),
    ("bfloat16", "bfloat16", 16), ("bfloat16", "bfloat16", 9),
    ("bfloat16", "float32", 16),
])
def test_head_nll_is_autodiff_of_the_plain_head_and_loss(
        dtype, kernel_dtype, length):
    """The head that makes its own loss (``head_nll``: logits, f32
    softmax, ``d_logits`` and both products in the gradient's forward
    rule) against plain autodiff of ``lm_head`` and the engine's loss
    over logits: the loss to an f32 ulp (it weights before it sums), the
    accuracy equal, both gradients to the order of accumulation in f32
    and to two bf16 ulps in bf16 (the rule rounds where autodiff rounds:
    the logits before the f32 softmax, ``d_logits`` before both
    products, each gradient to its operand's dtype)."""
    from distributed_learning_simulator_tpu.parallel.engine import (
        make_loss_fn)

    kernel, x, y, dtype = _head_case(dtype, kernel_dtype, length)
    mask = jnp.asarray([1.0, 1.0])
    params = {"kernel": kernel, "scale": jnp.float32(1.5)}

    def unmade(variables, x):
        p = variables["params"]
        return so.UnmadeLogits(p["kernel"], x * p["scale"], dtype), {}

    def plain(variables, x):
        p = variables["params"]
        return so.lm_head(p["kernel"], x * p["scale"], dtype=dtype), {}

    def run(apply):
        (loss, (acc, _)), grads = jax.jit(jax.value_and_grad(
            make_loss_fn(apply), argnums=(0, 1), has_aux=True,
        ))(params, x, y, mask)
        return loss, acc, grads

    loss, acc, (grads, d_x) = run(unmade)
    want_loss, want_acc, (want, want_d_x) = run(plain)
    assert float(loss) == pytest.approx(float(want_loss), rel=3e-7)
    assert float(acc) == float(want_acc)
    assert grads["kernel"].dtype == kernel.dtype and d_x.dtype == x.dtype
    # f32: the order of accumulation (the rule's products are einsums of
    # their own); bf16: two ulps of the rounded gradient.
    tol = 1e-6 if dtype == jnp.float32 else 2 * 2.0 ** -8
    for got, ref in ((grads["kernel"], want["kernel"]), (d_x, want_d_x),
                     (grads["scale"], want["scale"])):
        got, ref = (np.asarray(a, np.float64) for a in (got, ref))
        assert np.all(np.abs(got - ref) <= tol * np.max(np.abs(ref)))
    # Not differentiated (the evaluation), it is the plain forward.
    sums, correct = unmade({"params": params}, x)[0].weighted_nll(
        y, jnp.ones(y.shape))
    logits = plain({"params": params}, x)[0]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    want_sum = -jnp.sum(jnp.take_along_axis(logp, y[..., None], -1))
    assert float(sums) == pytest.approx(float(want_sum), rel=3e-7)
    assert float(correct) == float(jnp.sum(jnp.argmax(logits, -1) == y))


def test_one_local_step_makes_the_logits_once():
    """One local step's loss and gradient hold three products of the
    head's shape (the logits, ``d_x``, ``d_kernel``) and hand nothing of
    vocabulary width from the gradient's forward rule to its backward
    rule: the residuals are the two gradients and two per-position
    vectors. With a plain head handing logits to the engine's loss, the
    same program keeps vocabulary-wide residuals."""
    from distributed_learning_simulator_tpu.parallel.engine import (
        make_loss_fn)

    model = get_model("solar_open2", num_classes=96, **share_args())
    params = make_params()
    tokens = jnp.zeros((2, 40), jnp.int32)  # 80 positions: 96 is the head
    mask = jnp.ones((2,))
    wide = (2, 40, 96)

    def head_products(jaxpr):
        return [
            eqn for eqn in _equations(jaxpr)
            if eqn.primitive.name == "dot_general" and 96 in {
                d for v in (*eqn.invars, *eqn.outvars)
                for d in v.aval.shape}
        ]

    def residual_shapes(apply):
        _, pull = jax.vjp(
            lambda p: make_loss_fn(apply)(p, tokens, tokens, mask)[0],
            params)
        return [leaf.shape for leaf in jax.tree_util.tree_leaves(pull)]

    step = jax.value_and_grad(make_loss_fn(model.apply), has_aux=True)
    jaxpr = jax.make_jaxpr(step)(params, tokens, tokens, mask).jaxpr
    assert len(head_products(jaxpr)) == 3
    assert wide not in residual_shapes(model.apply)

    def plain_apply(variables, x):
        head, counts = model.apply(variables, x)
        return head.logits(), counts

    assert wide in residual_shapes(plain_apply)


# --- through run_simulation --------------------------------------------------

TOKENS = {"vocab": 96, "length": 48}


def _token_dataset(seed=0, n_train=16, n_test=4):
    from distributed_learning_simulator_tpu.data.registry import Dataset

    rng = np.random.default_rng(seed)
    x = rng.integers(0, 96, (n_train + n_test, 49)).astype(np.int32)
    return Dataset("tokens", x[:n_train, :-1], x[:n_train, 1:],
                   x[n_train:, :-1], x[n_train:, 1:], 96)


def _run(tmp_path, chunk, **over):
    from distributed_learning_simulator_tpu.config import get_config
    from distributed_learning_simulator_tpu.simulator import run_simulation
    from distributed_learning_simulator_tpu.telemetry import spans

    argv = [
        "--dataset_name", "tokens", "--model_name", "solar_open2",
        "--model_args", json.dumps(share_args(**over)),
        "--worker_number", "4", "--epoch", "1", "--batch_size", "2",
        "--round", "2", "--client_chunk_size", str(chunk),
        "--eval_batch_size", "4", "--optimizer_name", "sgd",
        "--learning_rate", "0.1", "--momentum", "0",
        "--distributed_algorithm", "fed", "--telemetry_level", "basic",
        "--span_trace", "on",
        "--log_root", str(tmp_path / f"log{chunk}"),
        "--compilation_cache_dir", "none",
    ]
    result = run_simulation(get_config(argv), dataset=_token_dataset())
    return result, spans.last_run().counters()


def test_one_client_in_flight_is_the_stacked_round(tmp_path):
    """A FedAvg round with a client axis of one (``--client_chunk_size
    1``: no batching, a scan over the clients) is the round with the
    four clients stacked under ``vmap``, at a size where both fit; the
    counters say which ran and what the expert layers were sent."""
    one, one_counts = _run(tmp_path, 1)
    stacked, stacked_counts = _run(tmp_path, 4)
    assert one_counts["client_axis_width"] == 1
    assert stacked_counts["client_axis_width"] == 4
    # The model's head makes its loss and its gradients itself.
    assert one_counts["head_backward_tied"] == 1
    assert stacked_counts["head_backward_tied"] == 1
    journal = one["span_summary"]["journal_path"]
    with open(journal) as f:
        events = [json.loads(line) for line in f]
    assert [e["attrs"]["value"] for e in events
            if e.get("cat") == "counter"
            and e["name"] == "head_backward_tied"] == [1]
    timeline = _module_at("scripts", "trace_timeline")
    assert "head_backward_tied: 1" in timeline.render_text(
        timeline.summarize([timeline.load_journal(journal)]))
    for a, b in zip(one["history"], stacked["history"]):
        for name in ("test_loss", "mean_client_loss"):
            assert a[name] == pytest.approx(b[name], rel=1e-5)
    assert one["history"][1]["test_loss"] < one["history"][0]["test_loss"]
    tree_close(one["global_params"], stacked["global_params"], 1e-4)
    # 2 rounds x 4 clients x 2 steps x 2 sequences x 48 positions, a layer.
    assert one_counts["routed_tokens"] == 4 * 2 * 4 * 2 * 2 * 48
    per_token_layer = (
        one_counts["local_expert_assignments"] / one_counts["routed_tokens"]
    )
    assert 0.7 < per_token_layer < 1.3  # top-2 of 8, 4 held: 1.0 expected
    assert one_counts["host_syncs"] == one_counts["rounds"] == 2
    load = one["history"][-1]["expert_load"]
    assert np.asarray(load["load"]).shape == (4, 4)
    assert sum(map(sum, load["load"])) * 2 == pytest.approx(
        one_counts["local_expert_assignments"], rel=0.2)


# --- the cells the benchmark has keep their programs ------------------------

# sha256 of ``jax.jit(...).lower(...).as_text()`` at the parent commit
# (cf115c9), CPU, this container's jax: the FedAvg round program and the
# server evaluation of ResNet-18 under the flags of ``resnet18_fed_c1000``
# (8 clients x 4 samples, chunks of 4) and with ``--mesh_devices 4``.
PARENT_LOWERED = {
    ("round", False):
        "8cf57ef11b009b32d05a40f659038be2e46657fe7e5e9e47051f8dcd279098f3",
    ("round", True):
        "132c9af62a148c5c1cb513968b4e7b812517f3dcb8bbc3a2351e68a429d3513c",
    ("eval", False):
        "6e7c42e7bfcef706b7ade66ef0980694fffeca4f64ef98ba43429346cfc9b5ef",
    ("eval", True):
        "6e7c42e7bfcef706b7ade66ef0980694fffeca4f64ef98ba43429346cfc9b5ef",
}


@pytest.mark.parametrize("mesh", [False, True], ids=["one_chip", "mesh4"])
def test_resnet_cells_lower_to_the_parents_programs(mesh):
    """What this PR generalised (the loss over positions, a model's
    counters, a client axis of one, targets of any shape) is decided
    while tracing: the image cells' round and evaluation programs lower
    to the text they lowered to before."""
    from distributed_learning_simulator_tpu.config import get_config
    from distributed_learning_simulator_tpu.factory import get_algorithm
    from distributed_learning_simulator_tpu.parallel.engine import (
        make_decoder, make_eval_fn, make_optimizer, make_reshaper)

    argv = ["--dataset_name", "cifar10", "--model_name", "resnet18",
            "--worker_number", "8", "--epoch", "1", "--batch_size", "2",
            "--client_chunk_size", "4", "--eval_batch_size", "8",
            "--optimizer_name", "sgd", "--learning_rate", "0.02",
            "--momentum", "0.9", "--local_compute_dtype", "bfloat16",
            "--distributed_algorithm", "fed"]
    if mesh:
        argv += ["--mesh_devices", "4"]
    cfg = get_config(argv)
    model = get_model("resnet18", num_classes=10)
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 32, 32, 3)))["params"])
    algo = get_algorithm("fed", cfg)
    eval_fn = make_eval_fn(model.apply, preprocess=make_reshaper((32, 32, 3)),
                           name="server_eval")
    algo.prepare(model.apply, eval_fn)
    round_fn = algo.make_round_fn(
        model.apply, make_optimizer("sgd", 0.02, momentum=0.9), 8,
        preprocess=make_decoder((32, 32, 3)),
        client_sizes=None if mesh else np.full((8,), 4.0, np.float32),
    )
    S = jax.ShapeDtypeStruct
    texts = {
        "round": jax.jit(round_fn).lower(
            params, None, S((8, 4, 3072), jnp.uint8), S((8, 4), jnp.int32),
            S((8, 4), jnp.float32), S((8,), jnp.float32), jax.random.key(0),
        ).as_text(),
        "eval": jax.jit(eval_fn).lower(
            params, S((2, 8, 3072), jnp.float32), S((2, 8), jnp.int32),
            S((2, 8), jnp.float32),
        ).as_text(),
    }
    assert {
        (name, mesh): hashlib.sha256(text.encode()).hexdigest()
        for name, text in texts.items()
    } == {k: v for k, v in PARENT_LOWERED.items() if k[1] is mesh}


def test_updates_are_summed_not_rounded_parameters():
    """With bf16 local state the round of one client at a time adds every
    step's update, before the stored parameters are rounded, into the f32
    aggregate: the global model's change is the f32 run's to a few
    percent, where the weighted mean of the clients' rounded parameters
    is several times the change away from it (updates far below a bf16
    ulp of the weights). A smooth model: what is left is where each
    gradient is taken."""
    from distributed_learning_simulator_tpu.parallel.engine import (
        make_local_train_fn, make_optimizer)

    rng = np.random.default_rng(0)
    params = {
        "w1": jnp.asarray(rng.standard_normal((32, 64)) / 6, jnp.float32),
        "w2": jnp.asarray(rng.standard_normal((64, 10)) / 8, jnp.float32),
    }

    def apply(variables, x):
        p = variables["params"]
        return jnp.tanh(x @ p["w1"].astype(x.dtype)) @ p["w2"].astype(x.dtype)

    clients, shard = 8, 8
    xs = jnp.asarray(rng.standard_normal((clients, shard, 32)), jnp.float32)
    ys = jnp.asarray(rng.integers(0, 10, (clients, shard)), jnp.int32)
    mask = jnp.ones((clients, shard), jnp.float32)
    keys = jax.random.split(jax.random.key(1), clients)
    weights = jnp.full((clients,), 1.0 / clients)
    build = functools.partial(
        make_local_train_fn, apply, make_optimizer("sgd", 1e-3),
        local_epochs=1, batch_size=4,
    )

    def mean_change(train):  # the weighted mean of the clients' parameters
        trained, _, _ = jax.vmap(train, in_axes=(None, None, 0, 0, 0, 0))(
            params, None, xs, ys, mask, keys)
        return jax.tree_util.tree_map(
            lambda t, p: jnp.tensordot(weights, t.astype(jnp.float32), 1) - p,
            trained, params)

    def summed_updates(add):
        total = jax.tree_util.tree_map(jnp.zeros_like, params)
        for c in range(clients):
            total, _, _ = add(total, weights[c], params, None, xs[c], ys[c],
                              mask[c], keys[c])
        return total

    def gap(a, b):
        diff = sum(float(jnp.sum((a[k] - b[k]) ** 2)) for k in b)
        return (diff / sum(float(jnp.sum(b[k] ** 2)) for k in b)) ** 0.5

    exact = mean_change(build())
    assert gap(summed_updates(build(accumulate_updates=True)), exact) < 1e-3
    bf16 = dict(compute_dtype=jnp.bfloat16)
    assert gap(mean_change(build(**bf16)), exact) > 3.0
    assert gap(
        summed_updates(build(accumulate_updates=True, **bf16)), exact
    ) < 0.05
