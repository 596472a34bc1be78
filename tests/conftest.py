"""Test configuration: fake 8-device CPU mesh.

The reference has no tests at all (SURVEY §4). This suite uses JAX's virtual
CPU devices as the "fake backend" the reference lacks: 8 host devices let the
multi-chip sharding path run in CI without TPU hardware. Must run before any
JAX backend initialization — hence the environment is set before the import.
The suite runs on CPU even on a machine that has an accelerator.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from distributed_learning_simulator_tpu.config import ExperimentConfig  # noqa: E402
from distributed_learning_simulator_tpu.data.registry import get_dataset  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _assert_cpu_mesh():
    assert len(jax.devices()) == 8, "conftest must provide 8 virtual CPU devices"


@pytest.fixture()
def tiny_config():
    """Small, fast config on the explicit synthetic dataset."""
    return ExperimentConfig(
        dataset_name="synthetic",
        model_name="mlp",
        distributed_algorithm="fed",
        worker_number=4,
        round=2,
        epoch=1,
        learning_rate=0.1,
        batch_size=32,
        n_train=512,
        n_test=256,
        log_level="WARNING",
        dataset_args={"difficulty": 0.5},
    )


@pytest.fixture(scope="session")
def tiny_dataset():
    return get_dataset("synthetic", n_train=512, n_test=256, seed=0,
                       difficulty=0.5)


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
