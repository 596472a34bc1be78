"""chip_smoke.py's body at tiny size on CPU, its refusal off-TPU, and the
compile-cache placement helper it relies on (utils/compile_cache.py)."""

import dataclasses
import importlib.util
import os
import subprocess
import sys

import jax
import pytest

from distributed_learning_simulator_tpu.utils.compile_cache import (
    configure_compilation_cache,
)

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
_SCRIPT = os.path.join(_REPO, "chip_smoke.py")


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def cache_setting():
    """The cache directory is process-global: put it back afterwards."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_smoke_body_passes_at_tiny_size(tiny_config, tmp_path, cache_setting):
    """The leg body is a function of a config: the same checks the chip
    run makes hold for a three-round tiny run on CPU (the platform check
    lives in main(), which this does not call)."""
    smoke = _load_smoke()
    cfg = dataclasses.replace(
        tiny_config, round=3, telemetry_level="basic",
        log_root=str(tmp_path / "log"), compilation_cache_dir=None,
    )
    leg = smoke.run_leg("tiny", cfg)
    assert leg["failed"] == []
    assert len(leg["test_loss"]) == 3
    assert leg["compiles_after_round_0"] == 0
    assert leg["setup_seconds"] > leg["steady_round_seconds"] > 0
    # CPU reports no memory statistics, and says so rather than a number.
    assert leg["peak_bytes_in_use"] == [None]


def test_smoke_body_fails_without_compile_count(tiny_config, tmp_path,
                                                cache_setting):
    """telemetry off leaves the recompile count untaken: a failed check,
    not a pass."""
    smoke = _load_smoke()
    cfg = dataclasses.replace(
        tiny_config, log_root=str(tmp_path / "log"),
        compilation_cache_dir=None,
    )
    assert smoke.run_leg("tiny", cfg)["failed"] == [
        "no_compile_after_round_0"
    ]


def test_smoke_refuses_cpu():
    """Off-TPU the script exits non-zero naming the platform it found and
    prints no result."""
    proc = subprocess.run(
        [sys.executable, _SCRIPT], cwd=_REPO, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120,
    )
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert proc.stdout == ""


def test_cache_dir_left_to_the_environment(monkeypatch, cache_setting):
    jax.config.update("jax_compilation_cache_dir", "/set/by/jax/from/env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    for field in (".jax_cache", "/elsewhere", None):
        assert configure_compilation_cache(field) == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == "/set/by/jax/from/env"


def test_cache_dir_resolves_from_the_checkout_not_the_cwd(
        monkeypatch, tmp_path, cache_setting):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    seen = []
    for cwd in (tmp_path, _REPO):
        monkeypatch.chdir(cwd)
        seen.append(configure_compilation_cache(".jax_cache"))
        assert jax.config.jax_compilation_cache_dir == seen[-1]
    assert seen == [os.path.join(_REPO, ".jax_cache")] * 2
    assert configure_compilation_cache("/abs/cache") == "/abs/cache"


def test_cache_none_means_no_cache(monkeypatch, cache_setting):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    configure_compilation_cache(".jax_cache")
    assert configure_compilation_cache(None) is None
    assert jax.config.jax_compilation_cache_dir is None
