"""Model registry: name -> flax module.

TPU-native replacement of the external model registry the reference leans on
(``--model_name`` flag, reference simulator.sh:1, resolved inside the external
``DefaultConfig.create_trainer``, reference simulator.py:47). Names are
case-insensitive; "lenet5" matches the reference launch script.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributed_learning_simulator_tpu.models.afmoe import afmoe
from distributed_learning_simulator_tpu.models.cnn import (
    MLP,
    CifarCNN,
    TpuCifarCNN,
)
from distributed_learning_simulator_tpu.models.jamba import jamba
from distributed_learning_simulator_tpu.models.lenet import LeNet5
from distributed_learning_simulator_tpu.models.olmo_hybrid import olmo_hybrid
from distributed_learning_simulator_tpu.models.resnet import ResNet18, ResNet34
from distributed_learning_simulator_tpu.models.solar_open2 import solar_open2

_MODELS = {
    "lenet5": LeNet5,
    "cnn": CifarCNN,
    "cifarcnn": CifarCNN,
    "cnntpu": TpuCifarCNN,
    "tpucnn": TpuCifarCNN,
    "resnet18": ResNet18,
    "resnet34": ResNet34,
    "mlp": MLP,
    # Token sequences; ``num_classes`` is the vocabulary held, and
    # ``--model_args`` the share (heads_held, experts_held, vocab_rows).
    "solaropen2": solar_open2,
    # Trinity-Mini's family: banded sliding-window attention with rotary
    # positions beside gated NoPE full attention, a leading dense layer;
    # ``--model_args`` the share (experts_held, expert_offset, vocab_rows).
    "afmoe": afmoe,
    # Olmo-Hybrid's family: a dense post-norm stack, Gated DeltaNet (one
    # decay a head) beside ungated full attention; ``--model_args`` the
    # vocabulary held (vocab_rows).
    "olmohybrid": olmo_hybrid,
    # Jamba's family: Mamba-1 mixers (a selective scan) beside MQA, a
    # dense pre-norm stack, a tied head; ``--model_args`` the share
    # (channels_held, heads_held, mlp_columns_held, vocab_rows).
    "jamba": jamba,
}


def registered_models():
    return sorted(set(_MODELS))


def get_model(name: str, num_classes: int = 10, **kwargs):
    """Instantiate a model by registry name."""
    key = name.lower().replace("-", "").replace("_", "")
    if key not in _MODELS:
        raise ValueError(
            f"unknown model {name!r}; registered: {registered_models()}"
        )
    return _MODELS[key](num_classes=num_classes, **kwargs)


def init_params(model, sample_input, seed: int = 0):
    """Initialize model params from a sample batch (pure-params models only)."""
    init = model.init
    sample_input = jnp.asarray(sample_input)
    if getattr(model, "jit_init", False):
        # A model too large to run op by op: one program that draws the
        # weights. The forward pass it traces is dead code to XLA but not
        # to its compile time, so it is traced over the fewest positions
        # the model takes (no parameter's shape depends on them).
        init = jax.jit(init)
        sample_input = sample_input[:, :model.init_positions]
    variables = init(jax.random.key(seed), sample_input)
    if set(variables.keys()) != {"params"}:
        raise ValueError(
            "models must be pure functions of params (no mutable collections); "
            f"got {sorted(variables.keys())}"
        )
    return variables["params"]
