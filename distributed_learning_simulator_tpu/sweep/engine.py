"""Sweep execution engine: vmapped seed fleets + a warm-program scheduler.

Two execution strategies behind the :class:`~distributed_learning_simulator_tpu.sweep.spec.SweepSpec`
front door (strategy selection + refusals live there):

* **vmapped fleet** — points that agree on every program-defining knob
  except the fleet axes (seed, learning_rate) stack on a new leading
  experiment axis: per-point model inits and RNG key chains become
  ``[E, ...]`` operands, per-point learning rates a length-E f32 factor
  vector, and ONE jitted program
  (``parallel/engine.make_experiment_round_fn``) trains every
  experiment per dispatch. Point ``i``'s metric history is bit-identical
  to a solo ``run_simulation`` with that seed on the shared data
  (verified: tests/test_sweep.py) — compile is paid once for the fleet.
  With ``mesh_devices > 1`` the EXPERIMENT axis is sharded over the mesh
  (each device owns E/n whole experiments — sweep points packed across
  chips; cohort shapes are per-experiment, so they always "allow").
  Under a mesh the RNG/cohort streams stay exact but metric values hold
  to reduction-order tolerance — the SPMD partitioner may re-associate
  intra-experiment reductions, the same documented contract as
  resident-vs-mesh fed runs (docs/ROBUSTNESS.md).

* **scheduled** — heterogeneous points group by
  ``utils/reporting.config_hash`` and each group runs sequentially
  through one warm program. Programs are cached under a SEED-NORMALIZED
  program key: the seed is a pure operand (model init + the key chain),
  so seed-varied groups share one compiled program even though their
  config hashes differ — per-point ``compile_reused`` records exactly
  which points rode a warm program. Points whose features the lean
  warm-program loop does not cover (mesh/streamed/async/telemetry/...)
  fall back to a full ``run_simulation`` with ``compile_reused=False``
  — recorded honestly, never silently.

Sweep-level checkpoint/resume: with ``sweep_dir`` set, every completed
point persists its result (``point_NNN.json``) and its per-round
records append to the sweep's ``metrics.jsonl`` (schema v8 ``sweep``
sub-object through the shared builder — utils/reporting.py). A killed
sweep resumes with ``sweep_resume=True``: persisted points load, only
the remainder executes — and because points are independent
(per-experiment RNG chains), the stitched results are bit-identical to
the uninterrupted sweep (tests/test_sweep.py).
"""

from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from distributed_learning_simulator_tpu.algorithms.base import RoundContext
from distributed_learning_simulator_tpu.config import SHAPLEY_ALGORITHMS
from distributed_learning_simulator_tpu.data.registry import get_dataset
from distributed_learning_simulator_tpu.factory import get_algorithm
from distributed_learning_simulator_tpu.models.registry import (
    get_model,
    init_params,
)
from distributed_learning_simulator_tpu.parallel.engine import (
    make_decoder,
    make_eval_fn,
    make_experiment_eval_fn,
    make_experiment_round_fn,
    make_optimizer,
    make_reshaper,
    pad_eval_set,
)
from distributed_learning_simulator_tpu.parallel.mesh import (
    make_mesh,
    replicate,
    shard_client_data,
)
from distributed_learning_simulator_tpu.sweep.spec import SweepSpec
from distributed_learning_simulator_tpu.utils.compile_cache import (
    configure_compilation_cache,
)
from distributed_learning_simulator_tpu.utils.logging import get_logger
from distributed_learning_simulator_tpu.utils.reporting import (
    build_round_record,
    config_hash,
)

#: Chaos hook (the robustness/chaos.py idiom): when set to integer k,
#: run_sweep raises after k newly-executed points have been persisted —
#: the harness tests/test_sweep.py uses to prove sweep-level resume
#: stitches bit-identically. Inert in production.
_CRASH_ENV = "DLS_SWEEP_CRASH_AFTER"

#: Axis name of the experiment mesh (vmapped fleet packing): distinct
#: from the solo simulator's "clients" axis — here each device owns
#: whole experiments, not client shards.
EXPERIMENT_AXIS = "experiments"


def _seed_key(seed: int):
    """The solo round loop's RNG root for ``config.seed`` — one
    definition shared by the fleet's stacked key chain and the lean
    scheduler loop, so every strategy replays ``run_simulation``'s
    ``jax.random.key(config.seed + 1)`` exactly."""
    return jax.random.key(seed + 1)


def _sweep_record(point, strategy: str, compile_reused: bool,
                  experiments: int | None = None) -> dict:
    """The schema-v8 ``sweep`` sub-object for one point's records."""
    rec = {
        "point": point.index,
        "seed": int(point.config.seed),
        "lr": float(point.config.learning_rate),
        "strategy": strategy,
        "group": config_hash(point.config),
        "compile_reused": bool(compile_reused),
    }
    if experiments is not None:
        rec["experiments"] = int(experiments)
    return rec


def _shared_data(base, dataset, client_data):
    """Resolve the sweep's ONE dataset + client partition (the base
    config's data seed — see sweep/spec.py's data contract)."""
    from distributed_learning_simulator_tpu.simulator import (
        build_client_data,
    )

    if dataset is None:
        dataset = get_dataset(
            base.dataset_name, data_dir=base.data_dir, seed=base.seed,
            n_train=base.n_train, n_test=base.n_test, **base.dataset_args,
        )
    if client_data is None:
        client_data = build_client_data(base, dataset)
    return dataset, client_data


class _Program:
    """One compiled round program + everything needed to run points
    through it: the warm unit the scheduler caches and the fleet builds
    once. Data device arrays are owned by the enclosing scheduler/fleet
    (shared across programs — one upload per sweep)."""

    def __init__(self, cfg, dataset, client_data, devices):
        from distributed_learning_simulator_tpu.simulator import (
            _assert_client_stack_feasible,
            _assert_residency_feasible,
            _auto_chunk_size,
        )

        self.model = get_model(
            cfg.model_name, num_classes=dataset.num_classes,
            **cfg.model_args,
        )
        # The init batch is kept so each point re-initializes with ITS
        # seed; proto_params serve shape/feasibility math only.
        self.init_batch = dataset.x_train[:1]
        self.proto_params = init_params(
            self.model, self.init_batch, seed=cfg.seed
        )
        if cfg.client_chunk_size == 0:  # auto, same resolution as solo
            cfg = dataclasses.replace(
                cfg,
                client_chunk_size=_auto_chunk_size(
                    cfg, self.proto_params, client_data.n_clients
                ),
            )
        self.cfg = cfg
        self.n_clients = client_data.n_clients
        self.optimizer = make_optimizer(
            cfg.optimizer_name, cfg.learning_rate,
            momentum=cfg.momentum, weight_decay=cfg.weight_decay,
        )
        self.algorithm = get_algorithm(cfg.distributed_algorithm, cfg)
        _assert_residency_feasible(
            cfg, self.proto_params, self.n_clients,
            client_data.x.nbytes + client_data.y.nbytes
            + client_data.mask.nbytes + client_data.sizes.nbytes,
        )
        if self.algorithm.materializes_client_stack:
            _assert_client_stack_feasible(
                cfg, self.proto_params, self.n_clients
            )
        eval_pre = make_reshaper(dataset.x_test.shape[1:])
        self.eval_fn = make_eval_fn(
            self.model.apply, preprocess=eval_pre, name="server_eval"
        )
        self.evaluate = jax.jit(self.eval_fn)
        self.algorithm.prepare(
            self.model.apply,
            make_eval_fn(self.model.apply, preprocess=eval_pre),
        )
        preprocess = (
            make_decoder(client_data.sample_shape)
            if client_data.compact else None
        )
        self.algorithm.check_cohort(self.n_clients)
        self.round_fn = self.algorithm.make_round_fn(
            self.model.apply, self.optimizer, self.n_clients,
            preprocess=preprocess, client_sizes=client_data.sizes,
        )
        self.round_jit = jax.jit(self.round_fn, donate_argnums=(1,))
        self.server_init = self.server_update_jit = None
        _server = self.algorithm.make_server_update()
        if _server is not None:
            self.server_init, server_update_fn = _server
            self.server_update_jit = jax.jit(
                server_update_fn, donate_argnums=(1, 2)
            )
        self.devices = devices  # (cx, cy, cmask, sizes, eval_batches)


def _device_arrays(cfg, dataset, client_data):
    """One upload of the shared data: packed client arrays + the padded
    eval set, reused by every program of the sweep."""
    eval_np = pad_eval_set(
        dataset.x_test, dataset.y_test, cfg.eval_batch_size, flatten=True
    )
    return (
        jnp.asarray(client_data.x), jnp.asarray(client_data.y),
        jnp.asarray(client_data.mask), jnp.asarray(client_data.sizes),
        tuple(jnp.asarray(a) for a in eval_np),
    )


def lean_supported(cfg) -> bool:
    """Whether the scheduler's lean warm-program loop covers this config.

    The lean loop replays ``run_simulation``'s core round sequence
    (split -> round_jit -> optional server step -> eval -> record) with
    deferred-fetch pipelining, bit-identically — but not the per-run
    machinery around it. Anything outside this envelope falls back to a
    full ``run_simulation`` with ``compile_reused=False`` (recorded, not
    silent).
    """
    return (
        cfg.execution_mode.lower() == "vmap"
        and not cfg.multihost
        and (cfg.mesh_devices or 1) <= 1
        and cfg.client_residency.lower() == "resident"
        and getattr(cfg, "population", "static").lower() == "static"
        and cfg.async_mode.lower() == "off"
        and cfg.client_stats.lower() == "off"
        and cfg.client_valuation.lower() == "off"
        and cfg.telemetry_level.lower() == "off"
        and not cfg.profile_dir
        and not cfg.cost_model_trace
        and not (cfg.checkpoint_dir and cfg.checkpoint_every)
        and not cfg.resume
        and cfg.distributed_algorithm not in SHAPLEY_ALGORITHMS
    )


def _emit_base_record(cfg, round_idx, metrics, mean_loss, fetched_tel,
                      extra, round_seconds) -> dict:
    """One round's v1-layout base record — delegated to the simulator's
    shared ``build_base_round_record`` (the ONE copy of the field set
    and insert order), so a sweep point's records can never drift from
    solo metrics.jsonl lines."""
    from distributed_learning_simulator_tpu.simulator import (
        build_base_round_record,
    )

    return build_base_round_record(
        cfg, round_idx, metrics, mean_loss, fetched_tel, extra,
        round_seconds=round_seconds,
    )


def _warmup_seconds(times: list[float]) -> float:
    """Explicit warmup accounting shared by every strategy's point
    summary: round 0's wall minus a steady round — the trace+compile
    cost the old harnesses silently dropped with ``history[1:]``."""
    if not times:
        return 0.0
    steady = times[1:]
    return round(
        max(times[0] - (float(np.median(steady)) if steady else 0.0), 0.0),
        4,
    )


class SweepScheduler:
    """The compile-cache-aware point runner (scheduled strategy).

    Programs are cached under a seed-normalized program key — the seed
    is a pure operand (model init + RNG chain), so seed-varied config
    hashes share one compiled program. Reusable OUTSIDE run_sweep too:
    bench.py routes its repeated same-program legs through one scheduler
    (warmup paid once, recorded — the ISSUE 11 small fix), and
    scripts/measure_scaling.py gets explicit per-point warmup
    accounting the silent ``history[1:]`` slice used to hide.
    """

    def __init__(self):
        self._programs: dict[str, _Program] = {}
        self._data_key = None
        self._devices = None
        # Live references to the dataset/client_data the cache was built
        # from: keeps the id()-based key honest (a collected object's id
        # can be recycled) and lets run() detect a data swap.
        self._data_ref = None
        self.points_run = 0
        self.programs_compiled = 0
        self.fallback_points = 0

    def program_key(self, cfg) -> str:
        """Seed-normalized program identity: every knob that defines the
        compiled program, with the seed (a pure operand) pinned. The
        learning rate stays IN the key — the lean loop bakes it into the
        optimizer exactly like a solo run, so lr-varied points honestly
        compile their own programs (the vmapped fleet is the strategy
        that operandizes lr)."""
        return config_hash(dataclasses.replace(cfg, seed=0))

    def _data(self, cfg, dataset, client_data):
        """Device arrays for the shared data — uploaded once. Swapping
        to DIFFERENT data invalidates every cached program (their
        round_fn closures captured the old arrays and client_sizes):
        the cache must never serve a warm program against data it was
        not built from."""
        key = (id(dataset), id(client_data), cfg.eval_batch_size)
        if self._data_key != key:
            if self._data_key is not None:
                self._programs.clear()
            self._devices = _device_arrays(cfg, dataset, client_data)
            self._data_key = key
            self._data_ref = (dataset, client_data)
        return self._devices

    def run(self, cfg, dataset=None, client_data=None):
        """Run one point; returns a result dict (history/final_accuracy/
        total_seconds/rounds_rejected/... — the run_simulation subset
        sweep consumers read) plus ``compile_reused`` and
        ``warmup_seconds``."""
        from distributed_learning_simulator_tpu.simulator import (
            run_simulation,
        )

        cfg.validate()
        dataset, client_data = _shared_data(cfg, dataset, client_data)
        self.points_run += 1
        # Same process-global compile-cache discipline as run_simulation:
        # honor (or reset) the config's persistent-cache setting before
        # any trace/compile happens.
        configure_compilation_cache(cfg.compilation_cache_dir)
        if not lean_supported(cfg):
            t0 = time.perf_counter()
            result = run_simulation(
                cfg, dataset=dataset, client_data=client_data,
                setup_logging=False,
            )
            self.fallback_points += 1
            result["compile_reused"] = False
            result["warmup_seconds"] = _warmup_seconds(
                [h["round_seconds"] for h in result["history"]]
            )
            result["total_seconds"] = time.perf_counter() - t0
            return result
        # Data first: a swapped dataset/client_data clears the program
        # cache (stale closures) BEFORE any cache lookup.
        devices = self._data(cfg, dataset, client_data)
        key = self.program_key(cfg)
        prog = self._programs.get(key)
        reused = prog is not None
        if prog is None:
            prog = _Program(cfg, dataset, client_data, devices)
            self._programs[key] = prog
            self.programs_compiled += 1
        result = _run_point_lean(prog, cfg)
        result["compile_reused"] = reused
        return result


def _run_point_lean(prog: _Program, cfg) -> dict:
    """The warm-program point loop: run_simulation's core round sequence
    (host key split -> round_jit -> optional server step -> eval ->
    record), bit-identical by construction — the same eager split chain,
    the same jitted round program, the same eval scan — with the solo
    loop's deferred-fetch pipelining when nothing needs same-round
    host state. Everything outside this envelope (checkpointing,
    telemetry, streaming, ...) is gated out by ``lean_supported``.
    """
    from distributed_learning_simulator_tpu.simulator import (
        _lr_factor,
        _oom_hint,
    )

    if cfg.client_chunk_size == 0:
        # Adopt the program's auto-resolved chunk only — the point keeps
        # its OWN horizon/seed/schedule knobs.
        cfg = dataclasses.replace(
            cfg, client_chunk_size=prog.cfg.client_chunk_size
        )
    algorithm = prog.algorithm
    cx, cy, cmask, sizes, eval_batches = prog.devices
    global_params = init_params(prog.model, prog.init_batch, seed=cfg.seed)
    client_state = algorithm.init_client_state(
        prog.optimizer, global_params, prog.n_clients
    )
    server_state = (
        prog.server_init(global_params)
        if prog.server_init is not None else None
    )
    key = _seed_key(cfg.seed)
    lr_active = cfg.lr_schedule.lower() != "constant"
    history: list[dict] = []
    telemetry = {"rounds_rejected": 0, "survivor_counts": []}
    prev_metrics = None
    pipelined = (
        cfg.pipeline_rounds
        and algorithm.supports_round_pipelining
        and client_state is None
        and server_state is None
    )
    t_start = time.perf_counter()
    t_prev_done = t_start

    def finalize(p):
        nonlocal prev_metrics, t_prev_done
        tel_keys = [
            k for k in ("survivor_count", "round_rejected", "participants")
            if k in p["aux"]
        ]
        fetched_metrics, fetched_loss, fetched_tel = jax.device_get(
            (p["metrics_dev"], p["mean_loss_dev"],
             {k: p["aux"][k] for k in tel_keys})
        )
        metrics = {k: float(v) for k, v in fetched_metrics.items()}
        ctx = RoundContext(
            round_idx=p["round_idx"],
            global_params=p["new_global"],
            prev_global_params=p["prev_global"],
            sizes=sizes,
            aux=p["aux"],
            metrics=metrics,
            prev_metrics=prev_metrics,
            eval_batches=eval_batches,
            log_dir=None,
        )
        extra = algorithm.post_round(ctx) or {}
        now = time.perf_counter()
        record = _emit_base_record(
            cfg, p["round_idx"], metrics, fetched_loss, fetched_tel,
            extra, now - t_prev_done,
        )
        t_prev_done = now
        if record.get("round_rejected"):
            telemetry["rounds_rejected"] += 1
        if "survivor_count" in record:
            telemetry["survivor_counts"].append(record["survivor_count"])
        history.append(record)
        prev_metrics = metrics

    pending = None
    try:
        for round_idx in range(cfg.round):
            key, round_key = jax.random.split(key)
            lr_args = (
                (jnp.float32(_lr_factor(cfg, round_idx)),)
                if lr_active else ()
            )
            with _oom_hint(cfg, global_params, prog.n_clients):
                new_global, client_state, aux = prog.round_jit(
                    global_params, client_state, cx, cy, cmask, sizes,
                    round_key, *lr_args,
                )
                if prog.server_update_jit is not None:
                    srv_args = (global_params, new_global, server_state)
                    if "round_rejected" in aux:
                        srv_args += (aux["round_rejected"],)
                    new_global, server_state = prog.server_update_jit(
                        *srv_args
                    )
            with _oom_hint(cfg, global_params, prog.n_clients, site="eval"):
                metrics_dev = prog.evaluate(new_global, *eval_batches)
            entry = {
                "round_idx": round_idx,
                "new_global": new_global,
                "prev_global": global_params,
                "aux": aux,
                "metrics_dev": metrics_dev,
                "mean_loss_dev": aux.get("mean_client_loss", np.nan),
            }
            global_params = new_global
            if pipelined:
                prev_pending, pending = pending, entry
                if prev_pending is not None:
                    finalize(prev_pending)
            else:
                finalize(entry)
    finally:
        if pending is not None:
            finalize(pending)
    total = time.perf_counter() - t_start
    return {
        "history": history,
        "final_accuracy": history[-1]["test_accuracy"] if history else None,
        "total_seconds": total,
        "client_rounds_per_sec": (
            len(history) * prog.n_clients / max(total, 1e-9)
        ),
        "rounds_rejected": telemetry["rounds_rejected"],
        "mean_survivor_count": (
            float(np.mean(telemetry["survivor_counts"]))
            if telemetry["survivor_counts"] else None
        ),
        "warmup_seconds": _warmup_seconds(
            [h["round_seconds"] for h in history]
        ),
        "client_chunk_size": cfg.client_chunk_size,
    }


def _run_fleet(spec: SweepSpec, points, dataset, client_data,
               logger) -> list[dict]:
    """The vmapped seed/lr fleet: one jitted program, E experiments per
    dispatch (see module docstring). Returns per-point result dicts.

    ``points`` may be a subset of the spec's points (sweep resume reruns
    only the missing ones), but the program reference config — and the
    lr-factor base — is ALWAYS the spec's first point, so a resumed
    fleet's operands (hence its histories) are bit-identical to the
    uninterrupted run's.
    """
    fcfg = spec.points[0].config
    E = len(points)
    devices = _device_arrays(fcfg, dataset, client_data)
    cx, cy, cmask, sizes, eval_batches = devices
    prog = _Program(fcfg, dataset, client_data, devices)
    cfg = prog.cfg  # auto chunk resolved
    seeds = [p.config.seed for p in points]
    params_list = [
        init_params(prog.model, dataset.x_train[:1], seed=s) for s in seeds
    ]
    params_E = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *params_list
    )
    keys_E = jnp.stack([_seed_key(s) for s in seeds])
    # Per-point lr factors against the program's baked base lr: exact 1.0
    # for a pure seed fleet, so the operand multiply is bit-exact there; an
    # lr-varied point's factor semantics match config.lr_schedule's outer
    # multiplier.
    lr_mults = np.asarray(
        [p.config.learning_rate / fcfg.learning_rate for p in points],
        dtype=np.float32,
    )
    lr_schedule_active = cfg.lr_schedule.lower() != "constant"
    lr_active = lr_schedule_active or bool(np.any(lr_mults != 1.0))
    fleet_round = jax.jit(
        make_experiment_round_fn(prog.round_fn, lr_active),
        donate_argnums=(0, 1),
    )
    fleet_eval = jax.jit(
        make_experiment_eval_fn(prog.eval_fn, len(eval_batches))
    )
    mesh = None
    if cfg.mesh_devices and cfg.mesh_devices > 1:
        # Experiment-axis packing: each device owns E/n whole
        # experiments (spec.fleet_compatible refused non-divisible E).
        mesh = make_mesh(cfg.mesh_devices, axis_name=EXPERIMENT_AXIS)
        params_E = shard_client_data(params_E, mesh)
        keys_E = shard_client_data(keys_E, mesh)
        cx, cy, cmask = (
            replicate(cx, mesh), replicate(cy, mesh), replicate(cmask, mesh)
        )
        sizes = replicate(sizes, mesh)
        eval_batches = replicate(eval_batches, mesh)
        logger.info(
            "sweep fleet: %d experiments packed over %d mesh devices",
            E, cfg.mesh_devices,
        )
    from distributed_learning_simulator_tpu.simulator import _lr_factor

    histories: list[list[dict]] = [[] for _ in points]
    telemetry = [
        {"rounds_rejected": 0, "survivor_counts": []} for _ in points
    ]
    t_start = time.perf_counter()
    t_prev = t_start
    for round_idx in range(cfg.round):
        lr_args = ()
        if lr_active:
            lr_vec = jnp.asarray(
                lr_mults * np.float32(_lr_factor(cfg, round_idx))
            )
            if mesh is not None:
                lr_vec = shard_client_data(lr_vec, mesh)
            lr_args = (lr_vec,)
        params_E, keys_E, aux = fleet_round(
            params_E, keys_E, cx, cy, cmask, sizes, *lr_args
        )
        metrics_dev = fleet_eval(params_E, *eval_batches)
        tel_keys = [
            k for k in ("survivor_count", "round_rejected", "participants")
            if k in aux
        ]
        fetched_metrics, fetched_loss, fetched_tel = jax.device_get(
            (metrics_dev, aux.get("mean_client_loss", np.full(E, np.nan)),
             {k: aux[k] for k in tel_keys})
        )
        now = time.perf_counter()
        wall = now - t_prev
        t_prev = now
        for e, point in enumerate(points):
            metrics = {
                k: float(v[e]) for k, v in fetched_metrics.items()
            }
            tel_row = {k: fetched_tel[k][e] for k in tel_keys}
            record = _emit_base_record(
                point.config, round_idx, metrics, fetched_loss[e],
                tel_row, {},
                # One dispatch trains all E experiments: the honest
                # per-experiment wall is the amortized share — what the
                # sweep_amortization_ratio measures.
                wall / E,
            )
            if record.get("round_rejected"):
                telemetry[e]["rounds_rejected"] += 1
            if "survivor_count" in record:
                telemetry[e]["survivor_counts"].append(
                    record["survivor_count"]
                )
            histories[e].append(record)
    total = time.perf_counter() - t_start
    results = []
    for e, point in enumerate(points):
        results.append({
            "history": histories[e],
            "final_accuracy": (
                histories[e][-1]["test_accuracy"] if histories[e] else None
            ),
            "total_seconds": total / E,
            "rounds_rejected": telemetry[e]["rounds_rejected"],
            "mean_survivor_count": (
                float(np.mean(telemetry[e]["survivor_counts"]))
                if telemetry[e]["survivor_counts"] else None
            ),
            # The fleet compiles once; the compile is attributed to
            # point 0 so mean(compile_reused) = 1 - programs/points —
            # the same accounting as the scheduler.
            "compile_reused": e > 0,
            "warmup_seconds": _warmup_seconds(
                [h["round_seconds"] for h in histories[e]]
            ),
            "client_chunk_size": cfg.client_chunk_size,
        })
    return results


def _point_path(sweep_dir: str, index: int) -> str:
    return os.path.join(sweep_dir, f"point_{index:04d}.json")


def _persist_point(sweep_dir, point, summary, records) -> None:
    os.makedirs(sweep_dir, exist_ok=True)
    with open(_point_path(sweep_dir, point.index), "w") as f:
        json.dump(summary, f)
    with open(os.path.join(sweep_dir, "metrics.jsonl"), "a") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")


def _load_point(sweep_dir, point) -> dict | None:
    """A previously persisted result for this point, or None. The stored
    config_hash must match — a resumed sweep whose points changed must
    re-run them, never stitch foreign histories."""
    path = _point_path(sweep_dir, point.index)
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            saved = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if saved.get("config_hash") != config_hash(point.config) or (
        saved.get("rounds") != point.config.round
    ):
        return None
    return saved


def run_sweep(spec_or_config, dataset=None, client_data=None) -> dict:
    """Run a multi-experiment sweep; returns the sweep result dict.

    Accepts a validated :class:`SweepSpec` or an ``ExperimentConfig``
    whose sweep knobs are set (``SweepSpec.from_config``). ``dataset`` /
    ``client_data`` are the same injection points as ``run_simulation``
    — the whole sweep shares them (the base config's data).
    """
    spec = (
        spec_or_config if isinstance(spec_or_config, SweepSpec)
        else SweepSpec.from_config(spec_or_config)
    )
    spec.validate()
    logger = get_logger()
    strategy = spec.resolve_strategy()
    base = spec.base
    configure_compilation_cache(base.compilation_cache_dir)
    dataset, client_data = _shared_data(base, dataset, client_data)
    crash_after = os.environ.get(_CRASH_ENV)
    crash_after = int(crash_after) if crash_after else None
    results: dict[int, dict] = {}
    resumed: set[int] = set()
    if spec.sweep_dir and spec.resume:
        for point in spec.points:
            saved = _load_point(spec.sweep_dir, point)
            if saved is not None:
                results[point.index] = saved
                resumed.add(point.index)
        if resumed:
            logger.info(
                "sweep resume: %d/%d point(s) loaded from %s",
                len(resumed), len(spec.points), spec.sweep_dir,
            )
    if spec.sweep_dir and not spec.resume:
        # Fresh sweep into an existing dir: clear the previous sweep's
        # artifacts so records never interleave two sweeps (point files
        # would be overwritten anyway; metrics.jsonl appends).
        stale = os.path.join(spec.sweep_dir, "metrics.jsonl")
        if os.path.exists(stale):
            os.remove(stale)
        for p in spec.points:
            path = _point_path(spec.sweep_dir, p.index)
            if os.path.exists(path):
                os.remove(path)
    todo = [p for p in spec.points if p.index not in resumed]
    executed = 0
    t_start = time.perf_counter()

    def record_point(point, run_result, strategy_name):
        nonlocal executed
        sweep_rec = _sweep_record(
            point, strategy_name, run_result.get("compile_reused", False),
            # The EXECUTED fleet's width (a resumed fleet re-runs only
            # the missing points).
            experiments=(
                len(todo) if strategy_name == "vmapped" else None
            ),
        )
        records = [
            build_round_record(dict(h), sweep=sweep_rec)
            for h in run_result["history"]
        ]
        summary = {
            "index": point.index,
            "seed": int(point.config.seed),
            "learning_rate": float(point.config.learning_rate),
            "overrides": point.overrides,
            "config_hash": config_hash(point.config),
            "rounds": point.config.round,
            "strategy": strategy_name,
            "compile_reused": bool(run_result.get("compile_reused", False)),
            "warmup_seconds": run_result.get("warmup_seconds"),
            "final_accuracy": run_result.get("final_accuracy"),
            "total_seconds": round(run_result.get("total_seconds", 0.0), 4),
            "rounds_rejected": run_result.get("rounds_rejected", 0),
            "history": run_result["history"],
        }
        results[point.index] = summary
        if spec.sweep_dir:
            _persist_point(spec.sweep_dir, point, summary, records)
        executed += 1
        if crash_after is not None and executed >= crash_after:
            raise RuntimeError(
                f"sweep chaos crash after {executed} point(s) "
                f"({_CRASH_ENV})"
            )

    if strategy == "vmapped":
        # (A fully-resumed fleet has nothing to run — the strategy label
        # stays 'vmapped', matching the persisted per-point records.)
        if todo:
            fleet_results = _run_fleet(
                spec, todo, dataset, client_data, logger
            )
            for point, rr in zip(todo, fleet_results):
                record_point(point, rr, "vmapped")
        programs_compiled = 1 if todo else 0
    else:
        scheduler = SweepScheduler()
        # config_hash grouping: points of one hash run consecutively so
        # each group streams through its (seed-normalized) warm program.
        groups: dict[str, list] = {}
        for p in todo:
            groups.setdefault(config_hash(p.config), []).append(p)
        for group_points in groups.values():
            for point in group_points:
                rr = scheduler.run(
                    point.config, dataset=dataset, client_data=client_data
                )
                record_point(point, rr, "scheduled")
        programs_compiled = (
            scheduler.programs_compiled + scheduler.fallback_points
        )
    total = time.perf_counter() - t_start
    ordered = [results[p.index] for p in spec.points]
    n_exec = len(todo)
    reuse = (
        sum(1 for p in spec.points
            if p.index not in resumed and results[p.index]["compile_reused"])
        / n_exec if n_exec else None
    )
    finals = [
        (r["final_accuracy"], -r["index"]) for r in ordered
        if r["final_accuracy"] is not None
    ]
    winner = None
    if finals:
        best = max(finals)
        winner_idx = -best[1]
        winner = {
            "point": winner_idx,
            "seed": ordered[winner_idx]["seed"],
            "learning_rate": ordered[winner_idx]["learning_rate"],
            "final_accuracy": best[0],
        }
    out = {
        "strategy": strategy,
        "points": [
            {**r, "resumed": r["index"] in resumed} for r in ordered
        ],
        "n_points": len(spec.points),
        "executed_points": n_exec,
        "resumed_points": len(resumed),
        "programs_compiled": programs_compiled if n_exec else 0,
        "compile_reuse_fraction": reuse,
        "winner": winner,
        "total_seconds": total,
        "experiments_per_hour": (
            n_exec / total * 3600.0 if n_exec and total > 0 else None
        ),
        "sweep_dir": spec.sweep_dir,
    }
    # $/sweep (telemetry/costmodel.py): price the compiled program once,
    # multiply by the sweep's round occupancy per topology. Attached
    # when the base config names a trace of the (shared) program.
    if base.cost_model_trace:
        from distributed_learning_simulator_tpu.telemetry.costmodel import (
            ledger_totals,
            sweep_cost_record,
        )
        from distributed_learning_simulator_tpu.utils.tracing import (
            categorize_ops,
        )

        ledger = categorize_ops(base.cost_model_trace)
        if ledger and ledger_totals(ledger)["bytes_gb"] > 0:
            out["costmodel_sweep"] = sweep_cost_record(
                ledger,
                trace_rounds=base.cost_model_trace_rounds,
                points=len(spec.points),
                rounds_total=sum(r["rounds"] for r in ordered),
                programs_compiled=out["programs_compiled"],
                # Compile bookkeeping over the points THIS run executed
                # (a partial resume compiled programs only for them) —
                # keeps the cost record's reuse fraction equal to the
                # result dict's.
                executed_points=n_exec,
                anchor=base.cost_model_topology,
            )
        else:
            logger.warning(
                "cost_model_trace %r holds no byte-annotated device-op "
                "events; $/sweep pricing disabled", base.cost_model_trace,
            )
            out["costmodel_sweep"] = None
    logger.info(
        "sweep finished: %d point(s) (%d resumed), strategy=%s, "
        "programs_compiled=%s, compile_reuse=%.2f, %.2fs",
        len(spec.points), len(resumed), strategy,
        out["programs_compiled"],
        reuse if reuse is not None else float("nan"), total,
    )
    return out
