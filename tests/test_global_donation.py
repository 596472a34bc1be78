"""The global model donated to the round program (PR 30): where the loop is
not pipelined, not batched, and nothing it runs takes the model a round
started from, ``run_simulation`` jits the round with ``donate_argnums=(0,
1)``: the new global is written into the old one's buffer. Tiny presets on
the CPU; what is asserted is what the program decides and what the compiler
reports, never a time."""

import dataclasses
import json
import threading

import jax
import numpy as np
import pytest

from distributed_learning_simulator_tpu.config import (
    ExperimentConfig, get_config)
from distributed_learning_simulator_tpu.simulator import run_simulation
from distributed_learning_simulator_tpu.telemetry import spans
from distributed_learning_simulator_tpu.utils.checkpoint import load_checkpoint
from test_solar_open2 import _token_dataset, share_args


def _one_in_flight(tmp_path, name, rounds, *extra):
    """The sequence cell's traffic (``fed_one_in_flight.json``): one client
    in flight, rounds not pipelined, plain FedAvg."""
    argv = [
        "--dataset_name", "tokens", "--model_name", "solar_open2",
        "--model_args", json.dumps(share_args()),
        "--worker_number", "4", "--epoch", "1", "--batch_size", "2",
        "--round", str(rounds), "--client_chunk_size", "1",
        "--pipeline_rounds", "false",
        "--eval_batch_size", "4", "--optimizer_name", "sgd",
        "--learning_rate", "0.1", "--momentum", "0",
        "--distributed_algorithm", "fed", "--telemetry_level", "basic",
        "--log_root", str(tmp_path / name),
        "--compilation_cache_dir", "none", *extra,
    ]
    result = run_simulation(get_config(argv), dataset=_token_dataset())
    return result, spans.last_run().counters()


class _RoundJit:
    """Stands in for ``jax.jit`` while ``run_simulation`` builds its
    programs: keeps the jitted round function, the ``donate_argnums`` it
    was given and the shapes of its first call; ``donate`` replaces the
    donation (the program has no option that does)."""

    def __init__(self, donate=None):
        self._jit = jax.jit
        self._donate = donate
        self.asked = self.jitted = self.shapes = None

    def __call__(self, fun, **kwargs):
        if getattr(fun, "__name__", "") != "round_fn":
            return self._jit(fun, **kwargs)
        self.asked = kwargs.get("donate_argnums")
        if self._donate is not None:
            kwargs["donate_argnums"] = self._donate
        self.jitted = self._jit(fun, **kwargs)

        def call(*args, **kw):
            if self.shapes is None:
                self.shapes = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    (args, kw))
            return self.jitted(*args, **kw)

        return call


def _f32_bytes(tree):
    return sum(4 * leaf.size for leaf in jax.tree_util.tree_leaves(tree))


def _equal_trees(a, b):
    assert (jax.tree_util.tree_structure(a)
            == jax.tree_util.tree_structure(b))
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                            jax.tree_util.tree_leaves(b)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (
            jax.tree_util.keystr(path))


def test_the_compiled_round_aliases_the_global(tmp_path, monkeypatch):
    """The donation is in the executable, not answered with a copy: the
    compiled round program aliases at least the parameters' f32 bytes of
    its output to its arguments, and with (1,) it aliases less than that.
    (What the aliasing frees is the TPU compiler's to say: the CPU's
    buffer assignment counts an aliased output among its temporaries.)"""
    seen = _RoundJit()
    monkeypatch.setattr(jax, "jit", seen)
    result, counters = _one_in_flight(tmp_path, "alias", 1)
    monkeypatch.undo()
    assert counters["global_donated"] == 1
    assert seen.asked == (0, 1)
    args, kw = seen.shapes
    param_bytes = _f32_bytes(result["global_params"])
    assert _f32_bytes(args[0]) == param_bytes
    donated = seen.jitted.lower(*args, **kw).compile().memory_analysis()
    assert donated.alias_size_in_bytes >= param_bytes
    kept = jax.jit(
        seen.jitted.__wrapped__, donate_argnums=(1,)
    ).lower(*args, **kw).compile().memory_analysis()
    assert kept.alias_size_in_bytes < param_bytes


def test_donated_rounds_are_the_undonated_rounds(tmp_path, monkeypatch):
    """Three rounds with the global donated and three with the donation
    taken out (``jax.jit`` wrapped by the test): the same history and the
    same parameters, to the last bit."""
    donated, counters = _one_in_flight(tmp_path, "donated", 3)
    assert counters["global_donated"] == 1
    seen = _RoundJit(donate=(1,))
    monkeypatch.setattr(jax, "jit", seen)
    kept, _ = _one_in_flight(tmp_path, "kept", 3)
    monkeypatch.undo()
    assert seen.asked == (0, 1)
    assert len(donated["history"]) == len(kept["history"]) == 3
    for a, b in zip(donated["history"], kept["history"]):
        for name in ("test_loss", "test_accuracy", "mean_client_loss"):
            assert a[name] == b[name], (a["round"], name)
    assert np.isfinite(donated["history"][-1]["test_loss"])
    _equal_trees(donated["global_params"], kept["global_params"])


def test_resume_then_graceful_stop_saves_the_live_global(
        tmp_path, monkeypatch):
    """A resumed global comes from the host and is donated in the first
    dispatch; a SIGTERM after two more rounds finishes the round in flight
    and saves off the cadence. Nothing on that path holds the donated
    model: the saved global is the returned one."""
    assert threading.current_thread() is threading.main_thread()
    ckpt = str(tmp_path / "ckpt")
    first, _ = _one_in_flight(
        tmp_path, "first", 1, "--checkpoint_dir", ckpt,
        "--checkpoint_every", "1")
    monkeypatch.setenv("DLS_CRASH_AT_ROUND", "2")
    monkeypatch.setenv("DLS_CRASH_KIND", "sigterm")
    config = dataclasses.replace(
        first["algorithm"].config, round=10, resume=True,
        checkpoint_every=0, log_root=str(tmp_path / "second"))
    stopped = run_simulation(config, dataset=_token_dataset())
    counters = spans.last_run().counters()
    assert counters["global_donated"] == 1
    assert stopped["preempted_at"] == 2
    assert [row["round"] for row in stopped["history"]] == [1, 2]
    saved = load_checkpoint(str(tmp_path / "ckpt" / "round_2.ckpt"))
    assert saved["round_idx"] == 2
    _equal_trees(saved["global_params"], stopped["global_params"])
    # and it moved: the resumed tree was trained, not handed back.
    start = load_checkpoint(str(tmp_path / "ckpt" / "round_0.ckpt"))
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(start["global_params"]),
                        jax.tree_util.tree_leaves(saved["global_params"])))


def _mlp_config(tmp_path, **over):
    """Plain FedAvg on the small MLP with pipeline_rounds off: donates."""
    return ExperimentConfig(**{**dict(
        dataset_name="synthetic", model_name="mlp",
        distributed_algorithm="fed", worker_number=4, round=2, epoch=1,
        learning_rate=0.1, batch_size=32, n_train=256, n_test=128,
        log_level="WARNING", dataset_args={"difficulty": 0.5},
        compilation_cache_dir=None, telemetry_level="basic",
        pipeline_rounds=False, log_root=str(tmp_path / "log"),
    ), **over})


# Each names the ONE thing that keeps the round program on (1,): all but
# the first run with pipeline_rounds off.
KEEPS_THE_GLOBAL = {
    "pipelined": dict(pipeline_rounds=True),
    "gtg_shapley": dict(distributed_algorithm="GTG_shapley_value"),
    "valuation_auditor": dict(
        client_stats="on", client_valuation="on", valuation_audit_every=1,
        valuation_audit_permutations=4),
    "server_optimizer": dict(server_optimizer_name="sgd"),
}


@pytest.mark.parametrize("case", sorted(KEEPS_THE_GLOBAL))
def test_a_consumer_of_the_previous_global_keeps_it(
        case, tmp_path, monkeypatch):
    """``global_donated`` reads 0 and the previous global reaches its
    consumer alive: ``post_round``'s context in every case, the auditor's
    replay where it is on (the server optimizer takes it inside the loop:
    a deleted array would raise there)."""
    from distributed_learning_simulator_tpu.algorithms.fedavg import FedAvg
    from distributed_learning_simulator_tpu.algorithms.shapley import (
        GTGShapley)
    from distributed_learning_simulator_tpu.telemetry.valuation import (
        ValuationAuditor)

    handed = {"post_round": [], "auditor": []}

    def alive(tree):
        leaves = jax.tree_util.tree_leaves(tree)
        return bool(leaves) and not any(l.is_deleted() for l in leaves)

    for cls in (FedAvg, GTGShapley):
        def post_round(self, ctx, _inner=cls.post_round):
            handed["post_round"].append(alive(ctx.prev_global_params))
            return _inner(self, ctx)

        monkeypatch.setattr(cls, "post_round", post_round)

    def audit(self, round_idx, round_key, prev_global, *a, **kw):
        handed["auditor"].append(alive(prev_global))
        return audit_inner(self, round_idx, round_key, prev_global, *a, **kw)

    audit_inner = ValuationAuditor.run
    monkeypatch.setattr(ValuationAuditor, "run", audit)

    result = run_simulation(
        _mlp_config(tmp_path, **KEEPS_THE_GLOBAL[case]), setup_logging=False)
    assert spans.last_run().counters()["global_donated"] == 0
    assert len(result["history"]) == 2
    assert handed["post_round"] == [True, True]
    # (the auditor has nothing to replay against in a run's first round)
    assert handed["auditor"] == (
        [True] if case == "valuation_auditor" else [])


def test_plain_fedavg_without_pipelining_donates_on_the_stacked_path(
        tmp_path):
    """The control of the cases above: the same run with none of them
    donates, whatever the width of the client axis."""
    result = run_simulation(_mlp_config(tmp_path), setup_logging=False)
    assert spans.last_run().counters()["global_donated"] == 1
    assert np.isfinite(result["history"][-1]["test_loss"])
