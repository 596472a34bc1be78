"""Small utility modules: tracing no-ops, multihost init, payload math."""

import numpy as np

from distributed_learning_simulator_tpu.telemetry.spans import NullTracer
from distributed_learning_simulator_tpu.utils.tracing import (
    profile_session,
)


def test_profile_session_noop_and_annotated_span():
    """No profile dir, telemetry off: the session is a no-op and a
    boundary is a bare (inert) profiler annotation."""
    with profile_session(None):
        with NullTracer().span("test_region", "phase", round_idx=3):
            x = np.arange(4).sum()
    assert x == 6


def test_profile_session_writes_trace(tmp_path):
    import jax.numpy as jnp

    with profile_session(str(tmp_path / "trace")):
        _ = jnp.ones(8).sum()
    assert (tmp_path / "trace").exists()


def test_parse_device_trace_shape_and_robustness(tmp_path):
    """parse_device_trace returns the proxy dict for a real trace dir and
    zeros (not an exception) for an empty one."""
    import jax
    import jax.numpy as jnp

    from distributed_learning_simulator_tpu.utils.tracing import (
        parse_device_trace,
    )

    with profile_session(str(tmp_path / "trace")):
        _ = jax.jit(lambda x: (x * 2).sum())(jnp.ones(64)).block_until_ready()
    stats = parse_device_trace(str(tmp_path / "trace"))
    assert set(stats) == {"device_ms", "bytes_gb", "op_count"}
    assert stats["device_ms"] >= 0.0 and stats["bytes_gb"] >= 0.0
    empty = parse_device_trace(str(tmp_path / "nonexistent"))
    assert empty == {"device_ms": 0.0, "bytes_gb": 0.0, "op_count": 0}


def test_multihost_initialize_single_process():
    """On a single process, initialize is a no-op that reports devices."""
    from distributed_learning_simulator_tpu.parallel.multihost import (
        initialize_multihost,
    )

    n = initialize_multihost()
    assert n >= 1


def test_oom_hint_rewrites_device_oom():
    import jax
    import jax.numpy as jnp
    import pytest

    from distributed_learning_simulator_tpu.config import ExperimentConfig
    from distributed_learning_simulator_tpu.simulator import _oom_hint

    cfg = ExperimentConfig(worker_number=1000, client_chunk_size=250)
    params = {"w": jnp.zeros((1000, 100), jnp.float32)}
    with pytest.raises(RuntimeError, match="client_chunk_size="):
        with _oom_hint(cfg, params, 1000):
            raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm")
    # non-OOM errors pass through untouched
    with pytest.raises(jax.errors.JaxRuntimeError, match="something else"):
        with _oom_hint(cfg, params, 1000):
            raise jax.errors.JaxRuntimeError("something else")


def test_payload_accounting():
    import jax.numpy as jnp

    from distributed_learning_simulator_tpu.ops.payload import (
        compression_ratio,
        payload_bytes,
        quantized_payload_bytes,
        sign_payload_bytes,
    )

    tree = {"a": jnp.zeros((10, 10), jnp.float32), "b": jnp.zeros((50,), jnp.float32)}
    raw = payload_bytes(tree)
    assert raw == 150 * 4
    q = quantized_payload_bytes(tree, 256)
    assert q < raw
    s = sign_payload_bytes(tree)
    assert s < q
    assert compression_ratio(raw, q) > 1.0


def test_stochastic_round_bf16_unbiased():
    """_sr_to_bf16's hash dither must be unbiased: averaged over many
    salts, E[rounded] recovers values BETWEEN bf16 grid points (the
    property bf16 local training's accuracy rests on), and grid points
    round exactly."""
    import jax.numpy as jnp
    import numpy as np

    from distributed_learning_simulator_tpu.parallel.engine import _sr_to_bf16

    # values straddling bf16 grid points at several magnitudes
    base = np.array([1.0, 0.1, 0.01, -1.0, -0.25, 3.7], np.float32)
    ulp = np.float32(2.0) ** (np.floor(np.log2(np.abs(base))) - 7)
    x = jnp.asarray(base + 0.37 * ulp)  # 37% of the way to the next point

    acc = np.zeros_like(base, np.float64)
    n_salts = 4096
    salt = jnp.uint32(12345)
    for _ in range(n_salts):
        r, salt = _sr_to_bf16(x, salt)
        acc += np.asarray(r, np.float64)
    mean = acc / n_salts
    # mean must sit within a few percent of one ulp from the true value
    err_ulps = np.abs(mean - np.asarray(x, np.float64)) / ulp
    assert np.all(err_ulps < 0.05), err_ulps

    # exact bf16 grid values are returned exactly (dither only touches the
    # truncated low bits, which are zero on the grid)
    grid = np.asarray(
        jnp.asarray(base).astype(jnp.bfloat16).astype(jnp.float32)
    )
    r, _ = _sr_to_bf16(jnp.asarray(grid), jnp.uint32(7))
    np.testing.assert_array_equal(np.asarray(r, np.float32), grid)


def test_stochastic_round_decorrelated_across_salts():
    """Different salts (= different clients) must make independent rounding
    decisions for the same input value — the aggregate's unbiasedness
    rests on this (see engine._sr_to_bf16)."""
    import jax.numpy as jnp
    import numpy as np

    from distributed_learning_simulator_tpu.parallel.engine import _sr_to_bf16

    ulp = np.float32(2.0 ** -7)
    # mid-gap values with per-element sub-ulp jitter: real weights never
    # collide bit-exactly, and the hash dithers per VALUE — identical
    # bit patterns round identically within one salt (unlike a counter
    # PRNG), which is fine for continuous-valued weights
    jitter = (np.arange(256, dtype=np.float32) - 128) * np.float32(2e-5)
    x = jnp.asarray(1.0 + (0.5 + jitter) * ulp, jnp.float32)
    r1, _ = _sr_to_bf16(x, jnp.uint32(1))
    r2, _ = _sr_to_bf16(x, jnp.uint32(2))
    up1 = np.asarray(r1, np.float32) > 1.0
    up2 = np.asarray(r2, np.float32) > 1.0
    # each salt mixes up/down across elements, and salts disagree often
    assert 0.2 < up1.mean() < 0.8
    assert 0.2 < up2.mean() < 0.8
    assert (up1 != up2).mean() > 0.2


def test_package_main_entry_help():
    """`python -m distributed_learning_simulator_tpu` exposes the same CLI
    as the .simulator module (reference's `python3 simulator.py` entry)."""
    import os
    import subprocess
    import sys

    repo = os.path.join(os.path.dirname(__file__), "..")
    proc = subprocess.run(
        [sys.executable, "-m", "distributed_learning_simulator_tpu",
         "--help"],
        cwd=repo, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0
    assert "--distributed_algorithm" in proc.stdout


def test_profile_from_round_defers_trace(tmp_path, tiny_config):
    """config.profile_from_round starts the trace mid-run (bench.py's
    flagship proxy uses it to keep round 0's compile out of the traced
    window). The trace dir must exist and parse; a from_round past the
    last round must produce NO trace session (the stack never enters)."""
    import dataclasses
    import os

    from distributed_learning_simulator_tpu.simulator import run_simulation

    traced = str(tmp_path / "tr")
    cfg = dataclasses.replace(
        tiny_config, round=3, profile_dir=traced, profile_from_round=1,
    )
    res = run_simulation(cfg, setup_logging=False)
    assert len(res["history"]) == 3
    assert os.path.isdir(traced)
    # The deferral must be visible in the captured events: the loop's
    # `round` annotations (the tracer's spans; the round number is
    # their metadata) for rounds >= from_round are in the trace, round
    # 0's is NOT (a regression that starts the trace at round 0 would
    # put it in here). Their children are there under their own names.
    import glob
    import gzip
    import json

    rounds, names = set(), set()
    for path in glob.glob(
        os.path.join(traced, "**", "*.trace.json.gz"), recursive=True
    ):
        with gzip.open(path, "rt") as f:
            for ev in json.load(f).get("traceEvents", []):
                args = ev.get("args") or {}
                if "cat" not in args:
                    continue
                names.add(ev["name"])
                if ev["name"] == "round":
                    assert args["cat"] == "iter"
                    rounds.add(int(args["round"]))
    assert rounds == {1, 2}, rounds
    assert {"dispatch", "eval_dispatch", "host_sync", "record"} <= names
    assert not any(n.startswith("fl_round") for n in names), names

    never = str(tmp_path / "never")
    cfg2 = dataclasses.replace(
        tiny_config, round=2, profile_dir=never, profile_from_round=99,
    )
    res2 = run_simulation(cfg2, setup_logging=False)
    assert len(res2["history"]) == 2
    assert not os.path.isdir(never)  # trace never started


def test_run_artifact_paths_unique_same_second(tmp_path):
    """Two runs starting within the same second (even the same
    microsecond, forced via an identical explicit timestamp) must get
    DISTINCT log files and artifacts dirs — the collision that used to
    overwrite logs and interleave metrics.jsonl (utils/logging.py keyed
    paths on int(timestamp))."""
    import logging as _logging
    import os

    from distributed_learning_simulator_tpu.utils.logging import (
        get_logger,
        set_file_handler,
        set_run_artifacts,
    )

    ts = 1700000000.123456
    p1 = set_file_handler(str(tmp_path), "fed", "mnist", "lenet5",
                          timestamp=ts)
    p2 = set_file_handler(str(tmp_path), "fed", "mnist", "lenet5",
                          timestamp=ts)
    assert p1 != p2
    assert os.path.exists(p1) and os.path.exists(p2)
    # Sub-second precision + pid land in the run id.
    base = os.path.basename(p1)
    assert "123456" in base and str(os.getpid()) in base

    a1 = set_run_artifacts(str(tmp_path), "fed", "mnist", "lenet5")
    a2 = set_run_artifacts(str(tmp_path), "fed", "mnist", "lenet5")
    assert a1[0] != a2[0] and a1[1] != a2[1]
    assert os.path.isdir(a1[1]) and os.path.isdir(a2[1])

    # Detach the file sink this test attached (other tests share the
    # process-global logger).
    logger = get_logger()
    for h in [h for h in logger.handlers
              if isinstance(h, _logging.FileHandler)]:
        logger.removeHandler(h)
        h.close()


def test_profile_from_round_rejects_negative(tiny_config):
    """profile_from_round < 0 is a config error (caught in validate()
    alongside the other Shapley/profiling knob checks), not a silent
    never-starts-tracing run."""
    import dataclasses

    import pytest

    cfg = dataclasses.replace(tiny_config, profile_from_round=-1)
    with pytest.raises(ValueError, match="profile_from_round"):
        cfg.validate()
    # 0 (trace from the first round) stays valid.
    dataclasses.replace(tiny_config, profile_from_round=0).validate()
