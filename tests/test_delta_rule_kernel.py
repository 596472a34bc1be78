"""The Gated DeltaNet form of the delta rule as Pallas kernels
(ops/delta_rule.py): the kernels in Pallas's interpreter against the XLA
form (models/lm_parts.py ``chunked_delta_rule_xla``), output and all five
gradients, at the Olmo cell's head (keys 96, values 192, chunks of 64);
what chooses the kernels (the decay's shape, the shapes, the platform the
program is lowered for); the recorder's count; and the kernels compiled
for a described v5e at the cell's shapes."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from distributed_learning_simulator_tpu.models import lm_parts as parts
from distributed_learning_simulator_tpu.models import olmo_hybrid as oh
from distributed_learning_simulator_tpu.models.registry import get_model
from distributed_learning_simulator_tpu.ops import delta_rule as dr

DK, DV = 96, 192
NAMES = ("o", "dq", "dk", "dv", "d_log_decay", "d_beta")


def _case(batch, length, heads, decay, seed=0):
    """Inputs as the model makes them: ``q``, ``k`` l2-normed, ``q`` over
    ``sqrt(d_k)``, the log decay ``-decay * softplus``, ``beta`` in (0,
    2); a cotangent for the output."""
    keys = jax.random.split(jax.random.key(seed), 6)

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    shape = (batch, length, heads)
    q = l2(jax.random.normal(keys[0], shape + (DK,))) / DK ** 0.5
    k = l2(jax.random.normal(keys[1], shape + (DK,)))
    v = jax.nn.silu(jax.random.normal(keys[2], shape + (DV,)))
    g = -decay * jax.nn.softplus(jax.random.normal(keys[3], shape))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], shape))
    return (q, k, v, g, beta), jax.random.normal(keys[5], shape + (DV,))


def _results(rule, args, ct):
    """The output and the five gradients of ``<rule(...), ct>``."""
    out, vjp = jax.vjp(rule, *args)
    return (out,) + vjp(ct)


def _distance(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _xla(dtype):
    return lambda *a: parts.chunked_delta_rule_xla(*a, dtype)


def _kernels(dtype):
    return lambda *a: dr.delta_rule(*a, dtype=dtype, interpret=True)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("decay", [0.05, 2.0], ids=["weak", "strong"])
@pytest.mark.parametrize("heads,block,slab", [(4, 2, 2), (3, 6, 1)],
                         ids=["two_slabs_of_two", "one_block_of_three"])
def test_kernels_in_the_interpreter_are_the_xla_form(dtype, decay, heads,
                                                     block, slab,
                                                     monkeypatch):
    """Two sequences of three chunks, forward and backward (the backward
    walks the chunks in reverse), over four heads in two grid blocks of
    one slab of two (their ``[C, C]`` matrices the diagonal blocks of one
    ``[2C, 2C]``), or three heads, which no slab of two divides, in one
    grid block of three slabs of one. In f32 both forms make every
    product at ``HIGHEST``: they agree to 1e-5 of each result's norm. In
    bf16 both round the same operands of the products with the state
    (rounded differently in the backward: the kernel's adjoint is written
    by hand, the XLA form's is autodiff's), so each result of the kernels
    lies within 1e-2 of the XLA form's and no further from the f32 XLA
    form than 1.5 times the XLA form's own bf16 result does (which lies
    3-5e-3 from it): the kernels lose no precision."""
    monkeypatch.setattr(dr, "HEAD_BLOCK", block)
    dtype = jnp.dtype(dtype)
    args, ct = _case(2, 3 * dr.CHUNK, heads, decay, seed=1)
    assert dr.kernel_applies(3 * dr.CHUNK, DK, DV)
    assert (dr.head_block(heads), dr.stack(heads)) == (min(heads, block),
                                                       slab)
    got = _results(_kernels(dtype), args, ct)
    want = _results(_xla(dtype), args, ct)
    if dtype == jnp.float32:
        for name, a, b in zip(NAMES, got, want):
            assert _distance(a, b) < 1e-5, name
        return
    exact = _results(_xla(jnp.float32), args, ct)
    for name, a, b, c in zip(NAMES, got, want, exact):
        assert _distance(a, b) < 1e-2, name
        assert _distance(a, c) < 1.5 * _distance(b, c) + 1e-6, name


def test_the_decay_shape_the_shapes_and_the_platform_choose_the_form():
    """One decay a head over whole chunks lowers to the two kernels for a
    TPU and to the XLA form for the CPU, whose numbers are the XLA form's
    digit for digit; a decay per key channel, a length that is not whole
    chunks and a chunk that is not the kernels' keep the XLA form on
    both."""
    def loss(rule, **kw):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(rule(*a, jnp.float32, **kw)),
            argnums=tuple(range(5))))

    args, _ = _case(1, dr.CHUNK, 2, 1.0)
    traced = loss(parts.chunked_delta_rule).trace(*args)
    assert "platform_index" in str(traced.jaxpr)
    tpu = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert tpu.count("tpu_custom_call") == 2  # forward and backward
    assert dr.FORWARD_NAME in tpu and dr.BACKWARD_NAME in tpu
    assert "tpu_custom_call" not in traced.lower(
        lowering_platforms=("cpu",)).as_text()
    for got, want in zip(loss(parts.chunked_delta_rule)(*args),
                         loss(parts.chunked_delta_rule_xla)(*args)):
        assert jnp.array_equal(got, want)

    q, k, v, g, beta = args
    wide = jnp.broadcast_to(g[..., None], q.shape)
    odd, _ = _case(1, 100, 2, 1.0)
    assert not dr.kernel_applies(100, DK, DV)
    assert not dr.kernel_applies(dr.CHUNK, DK, DV, chunk=32)
    for rule_args, kw in (((q, k, v, wide, beta), {}), (odd, {}),
                          (args, {"chunk": 32})):
        jaxpr = str(loss(parts.chunked_delta_rule, **kw).trace(
            *rule_args).jaxpr)
        assert "platform_index" not in jaxpr and "pallas_call" not in jaxpr


@pytest.mark.parametrize("model,positions,layers", [
    (lambda: oh.olmo_hybrid(12544), 4096, 3),  # olmo_hybrid_fed_seq4k_c4
    (lambda: oh.olmo_hybrid(12544), 4000, 0),  # not whole chunks
    (lambda: get_model("solar_open2", num_classes=24576), 4096, 0),
    (lambda: get_model("afmoe", num_classes=25024), 8192, 0),
    (lambda: get_model("resnet18", num_classes=10), 3072, 0),
], ids=["olmo_cell", "olmo_odd_length", "solar_cell", "trinity_cell",
        "image_cell"])
def test_kernel_layers_by_model(model, positions, layers):
    """What ``run_simulation`` asks for ``delta_rule_kernel_layers``
    (reported on a TPU only, 0 anywhere else): the Olmo cell's three
    linear layers at 4,096 positions; Solar's rule has a decay per key
    channel and keeps the XLA form."""
    count = getattr(model(), "delta_rule_kernel_layers", lambda _: 0)
    assert count(positions) == layers


# --- compiled for a described v5e ----------------------------------------------

# Describing a TPU loads the TPU runtime into the process for good (its
# profiler then adds a device plane to every later capture), so the compile
# runs in a child process of its own. The persistent cache is off there: a
# compile for a described chip is written to it but cannot be read back
# without one.
_V5E_COMPILE = """
import json, sys
import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from distributed_learning_simulator_tpu.ops import delta_rule as dr

try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:  # whatever keeps libtpu away
    print(json.dumps({"skip": str(e)}))
    sys.exit(0)
jax.config.update("jax_enable_compilation_cache", False)
chip = SingleDeviceSharding(topo.devices[0])

def shape(*dims):
    return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=chip)

B, T, H = 1, 4096, 30
args = (shape(B, T, H, 96), shape(B, T, H, 96), shape(B, T, H, 192),
        shape(B, T, H), shape(B, T, H))

def rule(*a):
    return dr.delta_rule(*a, dtype=jnp.bfloat16)

def both(a, ct):
    return jax.vjp(rule, *a)[1](ct)

forward = jax.jit(rule).lower(*args).compile().as_text()
backward = jax.jit(both).lower(args, shape(B, T, H, 192)).compile().as_text()
print(json.dumps({"forward": dr.FORWARD_NAME in forward,
                  "backward": dr.BACKWARD_NAME in backward}))
"""


def test_the_kernels_compile_for_a_v5e_at_the_cells_shapes():
    """The rule forward and forward + backward at the Olmo cell's shapes
    (one sequence of 4,096 positions, 30 heads, bf16 products), compiled
    by the TPU's compiler for a chip that is described, not attached:
    what interpret mode cannot show (tiling, VMEM) it refuses here."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", _V5E_COMPILE], cwd=root,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    if "skip" in got:
        pytest.skip(f"no v5e:2x2 topology can be described here: "
                    f"{got['skip']}")
    assert got == {"forward": True, "backward": True}
