"""Simulation orchestrator: the TPU-native ``simulator.py``.

Replaces the reference entry point (reference simulator.py:33-72): where the
reference builds a thread pool, a queue-owning server, and one worker thread
per client, this builds

  dataset -> client partition (packed client axis) -> model/optimizer ->
  algorithm strategy -> ONE jitted round function -> host round loop.

The host loop only sequences rounds, evaluates the global model once per
round (parity with fed_server.py:85-86), logs, checkpoints, and runs the
algorithm's host-side post_round hook (Shapley). All training compute for all
clients in a round is a single XLA program launch.

Multi-chip: set ``config.mesh_devices`` — the packed client arrays and
per-client state get ``PartitionSpec("clients")`` over a 1-D mesh and the
same program runs SPMD; weighted-mean/vote reductions become ICI collectives.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import signal
import threading
import time
import zlib
from contextlib import ExitStack, contextmanager

import jax
import jax.numpy as jnp
import numpy as np

from distributed_learning_simulator_tpu.algorithms.base import RoundContext
from distributed_learning_simulator_tpu.config import ExperimentConfig
from distributed_learning_simulator_tpu.data.partition import (
    ClientData,
    dirichlet_partition,
    iid_partition,
    pack_client_shards,
)
from distributed_learning_simulator_tpu.data.registry import Dataset, get_dataset
from distributed_learning_simulator_tpu.data.residency import HostShardStore
from distributed_learning_simulator_tpu.factory import get_algorithm
from distributed_learning_simulator_tpu.models.registry import get_model, init_params
from distributed_learning_simulator_tpu.parallel.engine import (
    make_decoder,
    make_eval_fn,
    make_optimizer,
    make_reshaper,
    pad_eval_set,
)
from distributed_learning_simulator_tpu.parallel.mesh import (
    make_mesh,
    replicate,
    shard_client_data,
)
from distributed_learning_simulator_tpu.parallel.streaming import (
    CohortStreamer,
)
from distributed_learning_simulator_tpu.robustness.arrivals import (
    AsyncFederation,
)
from distributed_learning_simulator_tpu.robustness.chaos import maybe_crash
from distributed_learning_simulator_tpu.robustness.population import (
    PopulationModel,
    pop_key_words,
)
from distributed_learning_simulator_tpu.telemetry import (
    ClientStats,
    ClientValuation,
    ValuationAuditor,
    ValuationState,
    clock,
    costmodel_record,
    detect_and_record,
    hbm_limit_bytes,
    ledger_totals,
    log_round_compiles,
    peak_hbm_bytes,
    start_run,
    valuation_record,
)
from distributed_learning_simulator_tpu.telemetry.client_stats import (
    expert_load_record,
)
from distributed_learning_simulator_tpu.utils.reporting import (
    build_round_record,
)
from distributed_learning_simulator_tpu.utils.compile_cache import (
    configure_compilation_cache,
)
from distributed_learning_simulator_tpu.utils.errors import is_device_oom
from distributed_learning_simulator_tpu.utils.checkpoint import (
    gc_checkpoints,
    latest_checkpoint,
    load_latest_valid_checkpoint,
    save_checkpoint,
)
from distributed_learning_simulator_tpu.utils.logging import (
    get_logger,
    set_level,
    set_run_artifacts,
)
from distributed_learning_simulator_tpu.utils.tracing import (
    categorize_ops,
    profile_session,
)


def _f32_param_bytes(global_params) -> int:
    """f32 bytes of one model's params (works on arrays or ShapeDtypeStructs)."""
    return sum(
        leaf.size * 4 for leaf in jax.tree_util.tree_leaves(global_params)
    )


def _device_memory_limit() -> tuple[int, str]:
    """Per-device memory capacity and where the number came from.

    An accelerator reports ``bytes_limit``; one that does not is an
    error, not a guess. Only the CPU backend (which reports no memory
    stats) gets an assumed 16 GiB, so the CPU tests size chunks the way
    a v5e run would."""
    limit = hbm_limit_bytes()
    if limit:
        return limit, "reported by the device"
    platform = jax.local_devices()[0].platform
    if platform != "cpu":
        raise RuntimeError(
            f"{platform} device reports no memory capacity "
            "(memory_stats()['bytes_limit']); refusing to size "
            "per-client state against an assumed one"
        )
    return 16 * 1024**3, "assumed: the cpu backend reports none"


def _device_budget_bytes(config) -> float:
    """Usable device memory for per-client state: 60% of per-device HBM
    (:func:`_device_memory_limit`) times the mesh size (the client axis
    is split across mesh devices). The ONE copy of the budget model
    shared by the chunk auto-sizer, the OOM hint, and the
    materializing-path feasibility refusal."""
    return 0.6 * _device_memory_limit()[0] * (config.mesh_devices or 1)


def _persistent_state_factor(config) -> int:
    """Param-sized persistent per-client buffers: one per client for
    momentum sign_SGD or a persistent sgd optimizer, two for persistent
    adam. The one copy shared by the chunk auto-sizer and the residency
    feasibility check."""
    if (
        config.distributed_algorithm == "sign_SGD"
        and config.momentum != 0.0
    ):
        return 1
    if not config.reset_client_optimizer:
        return 2 if config.optimizer_name.lower() in ("adam", "adamw") else 1
    return 0


def _resident_clients(config, n_clients: int) -> int:
    """How many clients' persistent arrays are DEVICE-resident at once:
    the whole population under client_residency='resident', only the
    sampled cohort under 'streamed' (the host shard store owns the rest;
    data/residency.py)."""
    if config.client_residency.lower() == "streamed":
        return config.cohort_size(n_clients)
    return n_clients


def _auto_chunk_size(config, global_params, n_clients: int) -> int:
    """In-flight clients from the footprint model shared with the OOM
    diagnostics (_oom_hint derives its suggestion from this function):
    ~4x the f32 param bytes of transient state per in-flight client
    (grads + momentum + conv weight-grad temps incl. fragmentation)
    against the _device_budget_bytes budget, minus any PERSISTENT
    per-client state that is resident regardless of chunking
    (momentum-sign_SGD buffers, non-reset client optimizer state) — at
    POPULATION size when resident, cohort size under streamed residency
    (the budget the streaming layer exists to change).
    Validated on v5e: suggests ~57 for ResNet-18 x 1000 clients, inside
    the measured-safe 40-100 range."""
    param_bytes = _f32_param_bytes(global_params)
    budget = (
        _device_budget_bytes(config)
        - _persistent_state_factor(config)
        * _resident_clients(config, n_clients) * param_bytes
    )
    estimate = max(1, int(budget / (4 * param_bytes)))
    return min(estimate, config.cohort_size(n_clients))


def _assert_residency_feasible(config, global_params, n_clients: int,
                               data_bytes: int) -> None:
    """Refuse clearly when the per-client arrays cannot fit the device.

    Under the resident default every per-client array — the packed data
    shards AND any persistent algorithm state — is a device-resident
    ``[n_clients, ...]`` stack for the whole run; when that footprint
    exceeds the budget the run used to die as an opaque allocation
    failure deep inside the first dispatch. Name the fix instead:
    ``client_residency='streamed'`` keeps the full-N arrays in the host
    shard store and sizes HBM by the cohort (x2 for the double-buffered
    prefetch), which is what this check verifies in streamed mode.
    """
    budget = _device_budget_bytes(config)
    param_bytes = _f32_param_bytes(global_params)
    factor = _persistent_state_factor(config)
    streamed = config.client_residency.lower() == "streamed"
    if streamed:
        cohort = config.cohort_size(n_clients)
        per_client_data = data_bytes / max(n_clients, 1)
        # Sampled regime: two cohorts in flight — the computing
        # dispatch's slice plus the prefetched next one
        # (parallel/streaming.py double buffering). Full-cohort regime
        # (cohort == N, e.g. sign_SGD): ONE startup upload, resident
        # thereafter — no second buffer to budget for.
        buffers = 2 if cohort < n_clients else 1
        total = buffers * cohort * per_client_data + (
            factor * cohort * param_bytes
        )
        if total > budget:
            buf_note = (
                f"{buffers} (double-buffered) x " if buffers > 1
                else "1 (full-cohort, one startup upload) x "
            )
            raise ValueError(
                "client_residency='streamed' cohort footprint does not "
                f"fit: {buf_note}{cohort} cohort clients x "
                f"{per_client_data / 2**20:.1f} MB data + {factor} "
                f"param-sized state buffer(s) x {param_bytes / 2**20:.0f} "
                f"MB = {total / 2**30:.1f} GB, over the "
                f"~{budget / 2**30:.1f} GB device budget. Lower "
                "participation_fraction (the cohort) or raise "
                "mesh_devices (streamed residency shards the cohort "
                "slice over the mesh)."
            )
        return
    total = data_bytes + factor * n_clients * param_bytes
    if total > budget:
        state_note = (
            f" + {factor} param-sized state buffer(s) x {n_clients} "
            f"clients x {param_bytes / 2**20:.0f} MB"
            if factor else ""
        )
        raise ValueError(
            "client_residency='resident' keeps every per-client array "
            f"device-resident: {data_bytes / 2**30:.1f} GB of packed "
            f"data shards{state_note} = {total / 2**30:.1f} GB, over "
            f"the ~{budget / 2**30:.1f} GB device budget "
            f"({config.mesh_devices or 1} device(s)). Set "
            "client_residency='streamed' to keep the population host-side "
            "and stream only the sampled cohort, or use more mesh_devices."
        )


def _host_client_state(algorithm, optimizer, global_params, n_clients: int):
    """Full-N per-client state on the HOST (streamed residency).

    ``init_client_state`` builds a device stack — exactly what a
    million-client run must not do. Every init in the tree is
    per-client IDENTICAL (vmapped ``optimizer.init`` / broadcast
    zeros), so one client's row replicated N times is the same state
    the resident path would gather — the property the bit-identity
    contract between the residency modes rests on.
    """
    proto = algorithm.init_client_state(optimizer, global_params, 1)
    if proto is None:
        return None
    proto = jax.device_get(proto)
    return jax.tree_util.tree_map(
        lambda a: np.repeat(np.asarray(a), n_clients, axis=0), proto
    )


def _owned_device_tree(tree):
    """Device-place a host tree with buffers XLA exclusively owns.

    ``jnp.asarray`` of a numpy array is zero-copy on the CPU backend, so
    feeding the result to a ``donate_argnums`` position lets XLA write
    into (and free) memory the host side still holds — intermittent NaN
    histories or a hard interpreter abort depending on heap layout.
    Every host-originated tree that reaches a donated argnum (resumed
    client/server state, streamed state gathers) must go through here.
    """
    return jax.tree_util.tree_map(lambda a: jnp.array(a, copy=True), tree)


def _lr_factor(config, round_idx: int) -> float:
    """Per-round lr multiplier from config.lr_schedule (host-side scalar,
    passed into the jitted round program — no retrace across rounds)."""
    s = config.lr_schedule.lower()
    if s == "constant":
        return 1.0
    horizon = config.lr_schedule_rounds or config.round
    if s == "cosine":
        progress = min(round_idx / max(horizon - 1, 1), 1.0)
        return config.lr_min_factor + (1.0 - config.lr_min_factor) * 0.5 * (
            1.0 + math.cos(math.pi * progress)
        )
    # "step" (validate() guarantees the name set)
    return config.lr_step_gamma ** (round_idx // config.lr_step_size)


def build_base_round_record(config, round_idx: int, metrics: dict,
                            fetched_loss, fetched_tel: dict, extra: dict,
                            round_seconds: float) -> dict:
    """The v1-layout base of one round's metrics record — fields AND
    insert order. The ONE copy shared by ``run_simulation``'s
    emit_record and the sweep engine's lean/fleet loops
    (sweep/engine.py), so a sweep point's records can never drift from
    solo metrics.jsonl lines. ``extra`` is the algorithm's post_round
    dict (non-scalar values filtered exactly as before);
    ``round_seconds`` is the caller's wall attribution (between-round
    wall solo; the amortized dispatch share in a fleet)."""
    record = {
        "round": round_idx,
        "test_accuracy": metrics["accuracy"],
        "test_loss": metrics["loss"],
        "mean_client_loss": float(fetched_loss),
        "round_seconds": round_seconds,
        **{
            k: v for k, v in extra.items()
            if isinstance(v, (int, float, dict))
        },
    }
    if config.lr_schedule.lower() != "constant":
        record["lr_factor"] = _lr_factor(config, round_idx)
    if "survivor_count" in fetched_tel:
        record["survivor_count"] = int(fetched_tel["survivor_count"])
    if "round_rejected" in fetched_tel:
        record["round_rejected"] = bool(fetched_tel["round_rejected"])
    if "model_counts" in fetched_tel:
        record["expert_load"] = expert_load_record(fetched_tel["model_counts"])
    if "participants" in fetched_tel:
        # CRC of the sampled cohort: a compact per-round fingerprint
        # that lets the resume-determinism tests assert the cohort
        # sampling stream survives checkpoint/resume bit-exactly
        # without bloating metrics.jsonl with index lists.
        record["cohort_hash"] = zlib.crc32(
            np.ascontiguousarray(
                fetched_tel["participants"], dtype=np.int64
            ).tobytes()
        )
    return record


#: Per-round async-federation scalars the round program reports in aux
#: (robustness/arrivals.py; the carried ``async_state`` itself is popped
#: before any record building). Fetched inside the round's single metric
#: device_get, rendered as the schema-v4 ``async`` sub-object.
_ASYNC_AUX_KEYS = (
    "on_time_count", "late_count", "buffer_count", "buffer_applied",
    "mean_staleness", "sim_duration", "sim_duration_sync", "sim_clock",
)


def _algo_checkpoint_state(algorithm, metrics, server_state,
                           async_state=None, valuation=None,
                           population=None) -> dict:
    """Assemble the checkpoint's ``algo_state`` dict — the ONE copy shared
    by the round-loop checkpoint cadence and the SIGTERM force-write path
    (the copies were one field away from drifting). ``async_state`` is the
    staleness-buffer carry (robustness/arrivals.py) — persisted so an async resume replays the
    buffer bit-exactly, absent entirely for synchronous runs.
    ``valuation`` is the streaming per-client valuation vector
    (telemetry/valuation.py) — persisted so a resumed run keeps its
    accumulated contribution evidence; absent when the feature is off.
    ``population`` is the dynamic-population registration-stream payload
    (robustness/population.PopulationModel.checkpoint_state: cursor +
    alive mask + joined shard rows) — what makes a resume mid-growth
    stitch bit-identically; absent for static populations."""
    algo_state = {"prev_metrics": metrics}
    if hasattr(algorithm, "shapley_values"):
        algo_state["shapley_values"] = algorithm.shapley_values
    if server_state is not None:
        algo_state["server_opt_state"] = jax.device_get(server_state)
    if async_state is not None:
        algo_state["async_state"] = jax.device_get(async_state)
    if valuation is not None:
        algo_state["valuation"] = np.asarray(valuation)
    if population is not None:
        algo_state["population"] = population
    return algo_state


def _assert_client_stack_feasible(config, global_params, n_clients: int):
    """Refuse the materializing path clearly when it cannot fit.

    Algorithms whose ``materializes_client_stack`` is true (Shapley scoring,
    client_eval telemetry, robust aggregation rules) hold the FULL
    ``[n_clients, params]`` f32 stack resident —
    chunking bounds the training transients, not this stack. At large N x
    large model that dies as a generic device OOM deep inside dispatch;
    mirror MultiRoundShapley's explicit N>16 refusal with a sized error
    instead (same footprint/budget model as _auto_chunk_size)."""
    param_bytes = _f32_param_bytes(global_params)
    # The round program stacks only the SAMPLED cohort (fedavg.round_fn
    # trains n_participants clients), so that is what must fit.
    cohort = config.cohort_size(n_clients)
    stack_bytes = cohort * param_bytes
    # GTG's cumsum prefix walk (gtg_prefix_mode='cumsum') additionally
    # carries one f32 running-sum row per still-active permutation. Worst
    # case THREE stack-sized carry trees coexist at a wave boundary (the
    # previous wave's carry, its compacted gather, and the re-concatenated
    # outputs — _CumsumPrefixWalker.eval_block), so budget the stack 3x
    # over — reported as its own term so the message's arithmetic is the
    # arithmetic checked: the whole point of this check is a clear,
    # size-your-config-from-it refusal instead of a generic OOM mid-walk.
    carry_note = ""
    total_bytes = stack_bytes
    if (
        config.distributed_algorithm == "GTG_shapley_value"
        and getattr(config, "gtg_prefix_mode", "cumsum") == "cumsum"
    ):
        total_bytes = 3 * stack_bytes
        carry_note = (
            " plus up to 2 stack-sized cumsum-walk carry trees = "
            f"{total_bytes / 2**30:.1f} GB peak"
        )
    budget = _device_budget_bytes(config)
    if total_bytes > budget:
        raise ValueError(
            f"{config.distributed_algorithm!r} materializes the per-client "
            f"parameter stack: {cohort} clients x "
            f"{param_bytes / 2**20:.0f} MB = {stack_bytes / 2**30:.1f} GB"
            f"{carry_note}, "
            f"over the ~{budget / 2**30:.1f} GB device budget "
            f"({config.mesh_devices or 1} device(s)). Use fewer clients, a "
            "smaller model, or more mesh_devices."
        )


@contextmanager
def _oom_hint(config, global_params, n_clients: int, site: str = "round"):
    """Re-raise device OOMs with an actionable client_chunk_size suggestion.

    Wraps every point where an async-dispatched round can surface a
    RESOURCE_EXHAUSTED error (dispatch, eval, and the deferred metric fetch
    — with async dispatch an execution-time OOM appears at the next host
    sync, not necessarily at the call that caused it).

    Footprint model (measured on v5e): ~4x the f32 param bytes per
    in-flight client (grads + momentum + conv weight-grad temps, incl.
    fragmentation); budget 60% of per-device HBM times the mesh size (the
    chunk is split across mesh devices).
    """
    try:
        yield
    except jax.errors.JaxRuntimeError as e:
        if not is_device_oom(e):
            raise
        # In-flight clients = chunk bounded by the sampled cohort size.
        cohort = config.cohort_size(n_clients)
        current = min(config.client_chunk_size or cohort, cohort)
        eval_note = (
            f" This OOM surfaced at {site}: if lowering client_chunk_size "
            f"doesn't help, also lower eval_batch_size "
            f"(currently {config.eval_batch_size})."
            if site != "round" else ""
        )
        param_bytes = _f32_param_bytes(global_params)
        estimate = _auto_chunk_size(config, global_params, n_clients)
        suggestion = min(estimate, max(1, current // 2))
        if suggestion >= current:
            raise RuntimeError(
                "device memory exceeded even with "
                f"client_chunk_size={current}; the model "
                f"(~{param_bytes / 2**20:.0f} MB of params) may not fit this "
                "device — use a smaller model or more mesh devices."
                + eval_note
            ) from e
        raise RuntimeError(
            "device memory exceeded with "
            f"{current} clients in flight (per-client params/grads/momentum "
            "and activations scale with client_chunk_size). Try "
            f"client_chunk_size={suggestion}." + eval_note
        ) from e


def build_client_data(config: ExperimentConfig, dataset: Dataset) -> ClientData:
    """Partition the training set into the packed client axis."""
    if config.partition == "iid":
        indices = iid_partition(
            len(dataset.x_train), config.worker_number, seed=config.seed
        )
    else:
        indices = dirichlet_partition(
            dataset.y_train, config.worker_number, config.dirichlet_alpha,
            seed=config.seed,
        )
    if config.max_shard_size:
        # Unbiased cap: partition index lists are dataset-ordered, so a
        # plain [:cap] would keep only low-index samples (dropping whole
        # classes on class-ordered datasets).
        rng = np.random.default_rng(config.seed + 17)
        indices = [
            rng.permutation(ix)[: config.max_shard_size] for ix in indices
        ]
    return pack_client_shards(
        dataset.x_train, dataset.y_train, indices,
        batch_size=config.batch_size,
        compact=config.compact_client_data,
    )


def run_simulation(
    config: ExperimentConfig,
    dataset: Dataset | None = None,
    client_data: ClientData | None = None,
    setup_logging: bool = True,
):
    """Run the full federated simulation; returns a result dict.

    ``dataset``/``client_data`` injection points cover the reference's
    heterogeneous-data variant (simulator_backup.py:71-77): build
    ``client_data`` yourself, call ``client_data.override_client(0, ...)``,
    and pass it in.
    """
    config.validate()
    # The ONE span recorder of the run (telemetry/spans.py), alive from
    # here to the return: in memory whenever telemetry is not 'off' (or
    # span_trace is 'on', which adds the journal and the flight recorder);
    # its null twin otherwise. ``telemetry.spans.last_run()`` reads it
    # afterwards.
    tracer = start_run(
        config.telemetry_level.lower(), config.span_trace.lower() == "on",
        capacity=config.span_buffer_size,
        flush_last_k=config.span_flush_last_k,
    )
    # No wrapper function and no ``with`` around the body: the root span
    # is opened and closed by hand so that the Python stack above the
    # first round's jit call stays exactly as deep as it was (a 216-byte
    # shift of it cost the flagship 6 s of set-up: PERF.md § 6, PR 25).
    tracer.start()
    try:
        tracer.section("setup/entry")
        # Cross-host clock alignment for span journals (telemetry/spans.py):
        # zeros for single-process runs; estimated once right after the
        # jax.distributed init barrier when tracing is on (the one moment
        # every host is provably inside the same code region).
        span_clock_offset = 0.0
        span_clock_unc = 0.0
        if config.multihost:
            # Before ANY device query or dispatch: jax.distributed must come up
            # first so the default backend enumerates every host's devices.
            from distributed_learning_simulator_tpu.parallel.multihost import (
                estimate_clock_alignment,
                initialize_multihost,
            )

            initialize_multihost(
                coordinator_address=config.coordinator_address,
                num_processes=config.num_processes,
                process_id=config.process_id,
            )
            if tracer.journal:
                span_clock_offset, span_clock_unc = estimate_clock_alignment()
                tracer.host_id = jax.process_index()
                tracer.n_hosts = jax.process_count()
        # Compilation-cache config comes BEFORE the execution-mode dispatch so
        # threaded runs (whose per-client local_train is jitted too) get the
        # persistent cache as well.
        configure_compilation_cache(config.compilation_cache_dir)
        if config.execution_mode.lower() == "threaded":
            if config.multihost:
                # The thread-per-client mode has no multi-process awareness;
                # each process would independently train ALL clients and write
                # a full artifact set — the silent split initialize_multihost's
                # contract forbids.
                raise ValueError(
                    "execution_mode='threaded' does not support multihost; "
                    "use the vmap execution mode"
                )
            # Honor the flag from EVERY entry point (heterogeneous CLI, bench,
            # programmatic callers), not just simulator.main.
            from distributed_learning_simulator_tpu.execution.threaded import (
                run_threaded_simulation,
            )

            return run_threaded_simulation(
                config, dataset=dataset, client_data=client_data,
                setup_logging=setup_logging,
            )
        logger = get_logger()
        set_level(config.log_level)
        # Multi-process SPMD runs one identical program per process; artifacts
        # (log file, metrics.jsonl, checkpoints) are written by process 0 only
        # — every process writing the same timestamped paths would interleave
        # log lines, duplicate every metrics record, and race checkpoint
        # writes into torn files.
        is_primary = jax.process_index() == 0
        log_dir = None
        if setup_logging and not is_primary:
            setup_logging = False
        if setup_logging:
            # Per-run artifact dir: Shapley metric pickles etc. go here so
            # concurrent/subsequent runs never overwrite each other's artifacts.
            log_path, log_dir = set_run_artifacts(
                config.log_root, config.distributed_algorithm,
                config.dataset_name, config.model_name,
            )
            logger.info("log file: %s", log_path)

        # --- data ---------------------------------------------------------------
        tracer.section("setup/data")
        if dataset is None:
            dataset = get_dataset(
                config.dataset_name, data_dir=config.data_dir, seed=config.seed,
                n_train=config.n_train, n_test=config.n_test,
                **config.dataset_args,
            )
        if client_data is None:
            client_data = build_client_data(config, dataset)
        n_clients = client_data.n_clients
        # Flat eval storage + in-program reshape: see make_reshaper's TPU
        # layout note (explicit NHWC input buffers pad 3-channel lanes to 128).
        eval_batches_np = pad_eval_set(
            dataset.x_test, dataset.y_test, config.eval_batch_size, flatten=True
        )
        eval_preprocess = make_reshaper(dataset.x_test.shape[1:])

        # --- model / optimizer / algorithm --------------------------------------
        tracer.section("setup/model_init")
        model = get_model(
            config.model_name, num_classes=dataset.num_classes,
            **config.model_args,
        )
        global_params = init_params(model, dataset.x_train[:1], seed=config.seed)
        if config.client_chunk_size == 0:  # auto
            # Resolve into a LOCAL copy: writing back to the caller's config
            # would freeze this model's footprint-derived chunk into an object
            # the caller may reuse with a different model (where auto should
            # re-resolve). The resolved value is logged and in the result dict.
            config = dataclasses.replace(
                config,
                client_chunk_size=_auto_chunk_size(
                    config, global_params, n_clients
                ),
            )
            limit, limit_source = _device_memory_limit()
            logger.info(
                "auto client_chunk_size=%d (footprint model, %s params, "
                "%.1f GiB per device %s)",
                config.client_chunk_size, config.model_name,
                limit / 2**30, limit_source,
            )
        optimizer = make_optimizer(
            config.optimizer_name, config.learning_rate,
            momentum=config.momentum, weight_decay=config.weight_decay,
        )
        algorithm = get_algorithm(config.distributed_algorithm, config)
        # Client-state residency (config.client_residency; data/residency.py +
        # parallel/streaming.py). 'resident' (default) keeps every per-client
        # array device-resident — the exact pre-feature program. 'streamed'
        # keeps the full-N arrays in a host shard store and uploads only the
        # sampled cohort per dispatch, double-buffered so the next dispatch's
        # cohort transfers while the current one computes.
        streamed = config.client_residency.lower() == "streamed"
        if streamed and not getattr(
            algorithm, "supports_streamed_residency", False
        ):
            raise ValueError(
                f"algorithm {config.distributed_algorithm!r} does not support "
                "client_residency='streamed': its round program assumes a "
                "device-resident per-client stack (the Shapley family's "
                "subset re-evaluation); set client_residency='resident'"
            )
        cohort_n = config.cohort_size(n_clients)
        # Sampling regime: per-dispatch cohort upload + prefetch + writeback.
        # Full-cohort regime (participation_fraction >= 1, e.g. sign_SGD):
        # the "cohort" is everyone — one startup upload, then the loop runs
        # the resident program shape (HBM already sizes by the cohort).
        stream_sampled = streamed and cohort_n < n_clients
        stream_full = streamed and not stream_sampled
        # Distributed shard store (streamed x multihost; data/residency.py +
        # parallel/streaming.DistributedCohortStreamer): with >1 host
        # process, each process owns an N/num_hosts client slice and serves
        # its own members of every round's owner-permuted cohort straight
        # into its addressable shards of the client-axis PartitionSpec.
        # Everything below is gated on mh, so a single process — including
        # multihost=True in a 1-process environment — runs the exact
        # single-host streamed path (the num_hosts==1 zero-cost contract).
        n_procs = jax.process_count()
        mh = streamed and config.multihost and n_procs > 1
        mh_mesh = None
        mh_owner_bounds = None
        mh_block_bounds = None
        if mh:
            from distributed_learning_simulator_tpu.data.residency import (
                host_axis_bounds,
            )
            from distributed_learning_simulator_tpu.parallel.multihost import (
                mesh_devices_per_host,
            )

            # The mesh is needed BEFORE placement here: ownership bounds
            # derive from its per-host device split, and the sharded-
            # checkpoint resume path validates the manifest against them.
            mh_mesh = make_mesh(config.mesh_devices)
            devs_per_host = mesh_devices_per_host(mh_mesh)
            mh_owner_bounds = host_axis_bounds(n_clients, devs_per_host)
            if stream_sampled:
                if cohort_n % config.mesh_devices != 0:
                    raise ValueError(
                        "cohort size (participation_fraction x "
                        f"worker_number) ({cohort_n}) must be a multiple "
                        f"of mesh_devices ({config.mesh_devices})"
                    )
                mh_block_bounds = host_axis_bounds(cohort_n, devs_per_host)
            else:
                # Full-cohort regime: the upload axis IS the client axis, so
                # ownership bounds and block bounds coincide.
                mh_block_bounds = mh_owner_bounds
        # Open-world population (config.population; robustness/population.py):
        # None at the 'static' default — the exact pre-feature path. Under
        # 'dynamic' the registration stream owns joins/departures/drift; the
        # cohort stays PINNED at this startup population's sampled size
        # (cohort_n), so the compiled round program never changes shape
        # while N grows. config.validate() already pinned the composition
        # (streamed + hashed + sampled + FedAvg family).
        pop = PopulationModel.from_config(
            config, n_clients, cohort_n, dataset=dataset
        )
        if pop is not None and not stream_sampled:
            raise ValueError(
                "population='dynamic' needs a sampled streamed cohort "
                f"(cohort {cohort_n} of {n_clients} clients is the whole "
                "population at this worker_number); raise worker_number or "
                "lower participation_fraction"
            )
        _assert_residency_feasible(
            config, global_params, n_clients,
            client_data.x.nbytes + client_data.y.nbytes
            + client_data.mask.nbytes + client_data.sizes.nbytes,
        )
        if algorithm.materializes_client_stack:
            _assert_client_stack_feasible(config, global_params, n_clients)
        if config.lr_schedule.lower() != "constant" and not getattr(
            algorithm, "supports_lr_schedule", False
        ):
            # Capability lives on the Algorithm class, not a config-level name
            # list: a third-party algorithm whose round_fn lacks the lr_scale
            # operand must fail HERE with the cause, not with an arity
            # TypeError at the first round dispatch.
            raise ValueError(
                f"algorithm {config.distributed_algorithm!r} does not support "
                "lr_schedule (its round program takes no lr_scale operand)"
            )
        # Asynchronous federation (robustness/arrivals.py): same capability
        # pattern as supports_lr_schedule — a refusal with the cause, not
        # a silent synchronous run the user didn't ask for.
        async_ctl = AsyncFederation.from_config(config)
        if async_ctl is not None and not getattr(
            algorithm, "supports_async", False
        ):
            raise ValueError(
                f"algorithm {config.distributed_algorithm!r} does not support "
                "async_mode='on': its round program has no staleness buffer "
                "to hold late uploads; set async_mode='off'"
            )

        # --- programs: eval, round, server update (jit wrappers) -----------------
        tracer.section("setup/build")
        eval_fn = make_eval_fn(
            model.apply, preprocess=eval_preprocess, name="server_eval"
        )
        evaluate = jax.jit(eval_fn)
        algorithm.prepare(
            model.apply, make_eval_fn(model.apply, preprocess=eval_preprocess)
        )
        preprocess = (
            make_decoder(client_data.sample_shape) if client_data.compact else None
        )
        # Count-dependent feasibility (exact Shapley's 2^N bound, GTG's
        # permutation cap) against the TRUE client count, for every algorithm
        # regardless of its make_round_fn inheritance (the threaded runner
        # makes the mirror call before its pool spawns).
        algorithm.check_cohort(n_clients)
        round_fn = algorithm.make_round_fn(
            model.apply, optimizer, n_clients, preprocess=preprocess,
            # Static per-client sample counts feed the size-aware work
            # scheduler (FedAvg fused path); withheld under mesh/multihost
            # sharding, where the client axis layout is owned by the
            # PartitionSpec.
            client_sizes=(
                None if config.multihost or (config.mesh_devices or 0) > 1
                else client_data.sizes
            ),
        )
        if stream_full:
            # Full-cohort streamed convention differs from the resident one
            # only by the idx operand (always None — the cohort is everyone).
            # Re-adapt so the round loop runs the SAME call shape as
            # resident — which is what makes this regime bit-identical by
            # construction.
            _streamed_fn = round_fn

            def round_fn(global_params, client_state, cx, cy, cmask, sizes,
                         key, lr_scale=1.0, async_state=None):
                kw = {} if async_state is None else {"async_state": async_state}
                return _streamed_fn(
                    global_params, client_state, cx, cy, cmask, sizes, None,
                    key, lr_scale, **kw,
                )

        tracer.set_counter(
            "local_steps_unrolled",
            algorithm.local_steps_unrolled(client_data.shard_size),
        )
        tracer.set_counter(
            "client_axis_width", min(config.client_chunk_size or n_clients,
                                     config.cohort_size(n_clients)),
        )
        # 1 where the model's head makes its loss and both its gradients
        # itself and hands on no logits (models/solar_open2.py head_nll).
        tracer.set_counter(
            "head_backward_tied",
            int(getattr(model, "head_backward_tied", False)),
        )
        # The banded attention's window, and the keys a block of queries
        # reads there at this data's positions (models/afmoe.py; the
        # positions themselves would say the band did not run); 0 where
        # no layer is windowed.
        tracer.set_counter(
            "attention_window", int(getattr(model, "attention_window", 0)),
        )
        tracer.set_counter(
            "swa_keys_per_query_block",
            int(getattr(model, "swa_keys_per_query_block", lambda _: 0)(
                dataset.x_train.shape[-1])),
        )
        # Layers whose softmax attention runs as the fused kernel
        # (models/lm_parts.py attention_core): the model's count by its
        # shapes at this data's positions, on the devices the round
        # program runs on; 0 on any platform but a TPU, where the XLA
        # form is what is lowered.
        tracer.set_counter(
            "fused_attention_layers",
            int(jax.devices()[0].platform == "tpu" and getattr(
                model, "fused_attention_layers", lambda _: 0)(
                    dataset.x_train.shape[-1])),
        )

        # Optional server-side optimizer (FedOpt; exceeds the reference): the
        # aggregate is post-processed by a jitted pseudo-gradient step.
        server_state = None
        server_update_fn = None
        server_update_jit = None
        _server = algorithm.make_server_update()
        if (
            _server is None
            and config.server_optimizer_name.lower() not in ("none", "")
        ):
            # Don't let a configured server optimizer silently no-op: only the
            # FedAvg family consumes it (SignSGD applies votes inside the round).
            raise ValueError(
                f"algorithm {config.distributed_algorithm!r} does not support a "
                "server optimizer; set server_optimizer_name='none'"
            )
        if _server is not None:
            server_init, server_update_fn = _server
            server_state = server_init(global_params)
            # Donate the consumed aggregate and the replaced opt state: neither
            # is referenced after the call (entry keeps only the updated state).
            server_update_jit = jax.jit(server_update_fn, donate_argnums=(1, 2))

        # --- per-client state (allocation belongs to model init) ----------------
        tracer.section("setup/model_init")
        start_round = 0
        prev_metrics: dict | None = None
        # Streaming valuation vector saved by an earlier run (applied after
        # placement, once the ValuationState — and, under streamed
        # residency, its host-store home — exists).
        resumed_valuation = None
        # Dynamic-population registration-stream state saved by an earlier
        # run (applied after placement: it grows the host store by the
        # checkpointed joined shards and restores the alive mask + cursor).
        resumed_population = None
        key = jax.random.key(config.seed + 1)
        if streamed:
            # Host-side init: the full-N state tree must never be built as a
            # device stack (that allocation is what streamed mode removes).
            # Under the distributed store each host initializes ONLY the
            # rows it owns — per-host state RAM scales as N/num_hosts like
            # the data shards (every init row is identical, so the sliced
            # init equals the full init's slice by construction).
            _n_state = (
                int(mh_owner_bounds[jax.process_index() + 1]
                    - mh_owner_bounds[jax.process_index()])
                if mh else n_clients
            )
            client_state = _host_client_state(
                algorithm, optimizer, global_params, _n_state
            )
        else:
            client_state = algorithm.init_client_state(
                optimizer, global_params, n_clients
            )
        # Staleness-buffer carry (async_mode='on'): one f32 param-sized
        # accumulator + scalars, owned by the host loop like client_state —
        # threaded into every dispatch, checkpointed, restored on resume.
        async_state = (
            async_ctl.init_state(global_params) if async_ctl is not None else None
        )
        # --- resume (before placement, so restored state gets sharded too) ------
        tracer.section("setup/resume")
        if config.resume and config.checkpoint_dir:
            from distributed_learning_simulator_tpu.utils.checkpoint import (
                load_latest_valid_sharded_checkpoint,
                manifest_rounds,
                validate_manifest,
            )

            if mh:
                # Per-host shards + manifest (utils/checkpoint.py): each
                # process restores its OWN shard; the manifest commits the
                # round and records the topology the shards were cut for.
                # The shard payload carries the same keys as a whole
                # checkpoint, so every structure/config check below runs
                # unchanged on it.
                manifest, ckpt = load_latest_valid_sharded_checkpoint(
                    config.checkpoint_dir, jax.process_index(), n_procs
                )
                if manifest is not None:
                    validate_manifest(
                        manifest, n_hosts=n_procs, n_clients=n_clients,
                        owner_bounds=mh_owner_bounds,
                    )
                    # The agreement check below hashes the MANIFEST name
                    # (identical across hosts); shard basenames differ per
                    # host by construction.
                    ckpt_path = os.path.join(
                        config.checkpoint_dir,
                        f"round_{manifest['round']}.manifest.json",
                    )
                else:
                    ckpt_path = None
                    if latest_checkpoint(config.checkpoint_dir):
                        raise RuntimeError(
                            "multihost streamed resume found only a "
                            "single-file checkpoint in "
                            f"{config.checkpoint_dir!r}: it was written by "
                            "a single-process run and cannot be re-split "
                            "into per-host shards; resume it on the "
                            "topology it was written with"
                        )
            else:
                # Integrity-verified discovery: a corrupt/truncated latest
                # checkpoint (CRC mismatch) is skipped with a warning and
                # resume falls back to the newest VALID one instead of
                # crashing.
                ckpt_path, ckpt = load_latest_valid_checkpoint(
                    config.checkpoint_dir
                )
                if ckpt_path is None and manifest_rounds(config.checkpoint_dir):
                    raise RuntimeError(
                        f"checkpoint dir {config.checkpoint_dir!r} holds "
                        "per-host sharded checkpoints (a multihost streamed "
                        "run wrote them); resume under the multihost "
                        "streamed topology they were written with — this "
                        "run is "
                        + ("multihost resident"
                           if config.multihost else "single-process")
                    )
            if ckpt_path:
                resumed_basename = os.path.basename(ckpt_path)
                want_gp = jax.tree_util.tree_structure(global_params)
                got_gp = jax.tree_util.tree_structure(ckpt["global_params"])
                if want_gp != got_gp:
                    # Fail here with the cause, not mid-apply with a missing-
                    # param error: e.g. a checkpoint written before a model's
                    # internal layout change (resnet18 fold_stage1 renames its
                    # block modules) or with a different model_name entirely.
                    raise ValueError(
                        "checkpoint global_params do not match this model's "
                        f"parameter structure ({config.model_name!r}); the "
                        "checkpoint was written with a different model or "
                        "model version — resume with the configuration it was "
                        "written with"
                    )
                # init_params' tree goes before the checkpoint's is placed
                # (two f32 copies of the model live at once are more than
                # any round holds); owned buffers, since the loop may
                # donate the global model.
                global_params = None
                global_params = _owned_device_tree(ckpt["global_params"])
                want_cs = jax.tree_util.tree_structure(client_state)
                got_cs = jax.tree_util.tree_structure(ckpt["client_state"])
                if want_cs != got_cs:
                    # e.g. a sign_SGD checkpoint written with momentum=0 has no
                    # per-client buffers (client_state=None) while momentum>0
                    # expects them — resuming across that mismatch would either
                    # crash inside jit or silently drop the saved buffers.
                    def _describe(ts) -> str:
                        n = ts.num_leaves
                        return "no per-client state" if n == 0 else (
                            f"per-client state with {n} leaves"
                        )

                    raise ValueError(
                        "checkpoint client_state does not match this "
                        "configuration (e.g. momentum / reset_client_optimizer "
                        "changed since the checkpoint was written): checkpoint "
                        f"has {_describe(got_cs)}, config expects "
                        f"{_describe(want_cs)}; resume with the configuration "
                        "the checkpoint was written with"
                    )
                # Streamed residency restores into the HOST shard store
                # (the source of truth between dispatches), not a device
                # stack; stream_full device-places it below. Resident state
                # is a donated round_jit operand, so it needs owned buffers.
                client_state = (
                    jax.tree_util.tree_map(np.asarray, ckpt["client_state"])
                    if streamed
                    else _owned_device_tree(ckpt["client_state"])
                )
                start_round = ckpt["round_idx"] + 1
                prev_metrics = ckpt["algo_state"].get("prev_metrics")
                if (
                    server_state is None
                    and ckpt["algo_state"].get("server_opt_state") is not None
                ):
                    raise ValueError(
                        "checkpoint was written with a server optimizer but "
                        "server_optimizer_name='none' now; resume with the "
                        "configuration the checkpoint was written with"
                    )
                if server_state is not None:
                    saved_ss = ckpt["algo_state"].get("server_opt_state")
                    if saved_ss is None:
                        logger.warning(
                            "checkpoint has no server optimizer state (written "
                            "before the feature or with a different config); "
                            "server optimizer restarts from fresh state"
                        )
                    else:
                        want = jax.tree_util.tree_structure(server_state)
                        got = jax.tree_util.tree_structure(saved_ss)
                        if want != got:
                            raise ValueError(
                                "checkpoint server optimizer state does not match "
                                f"server_optimizer_name="
                                f"{config.server_optimizer_name!r}; resume with "
                                "the configuration the checkpoint was written with"
                            )
                        # Donated by server_update_jit.
                        server_state = _owned_device_tree(saved_ss)
                saved_async = ckpt["algo_state"].get("async_state")
                if async_ctl is None and saved_async is not None:
                    raise ValueError(
                        "checkpoint was written with async_mode='on' but "
                        "async_mode='off' now (the staleness buffer would be "
                        "silently discarded); resume with the configuration "
                        "the checkpoint was written with"
                    )
                if async_ctl is not None:
                    if saved_async is None:
                        raise ValueError(
                            "async_mode='on' but the checkpoint has no "
                            "staleness-buffer state (written with "
                            "async_mode='off'); resume with the configuration "
                            "the checkpoint was written with"
                        )
                    async_state = jax.tree_util.tree_map(jnp.asarray, saved_async)
                if ckpt.get("rng_key") is not None:
                    key = ckpt["rng_key"]
                if hasattr(algorithm, "shapley_values"):
                    algorithm.shapley_values.update(
                        ckpt["algo_state"].get("shapley_values", {})
                    )
                resumed_valuation = ckpt["algo_state"].get("valuation")
                resumed_population = ckpt["algo_state"].get("population")
                if pop is not None and resumed_population is None:
                    raise ValueError(
                        "population='dynamic' but the checkpoint has no "
                        "registration-stream state (written with "
                        "population='static'); resume with the configuration "
                        "the checkpoint was written with"
                    )
                if pop is None and resumed_population is not None:
                    raise ValueError(
                        "checkpoint was written with population='dynamic' "
                        "but population='static' now (the grown population "
                        "and alive mask would be silently discarded); resume "
                        "with the configuration the checkpoint was written "
                        "with"
                    )
                logger.info("resumed from %s at round %d", ckpt_path, start_round)
            else:
                resumed_basename = ""
            if config.multihost and jax.process_count() > 1:
                # Checkpoints are written by process 0 only, but every process
                # restores independently from its own view of checkpoint_dir.
                # Without a shared filesystem the processes can restore
                # different rounds (or some none at all) and then dispatch
                # DIFFERENT numbers of SPMD round programs — a collective
                # mismatch (hang) or a silent split. Verify agreement before
                # any sharded dispatch; checkpoint_dir must be on storage all
                # hosts see (NFS/GCS-fuse) for multihost resume.
                from jax.experimental import multihost_utils

                local = np.asarray(
                    [start_round, zlib.crc32(resumed_basename.encode())],
                    dtype=np.int64,
                )
                gathered = multihost_utils.process_allgather(local)
                if not (gathered == gathered[0]).all():
                    raise RuntimeError(
                        "multihost resume mismatch: processes restored "
                        "different checkpoints (per-process [start_round, "
                        f"path_crc32] = {gathered.tolist()}); checkpoint_dir "
                        "must be a shared filesystem visible to every host "
                        "with an identical checkpoint set"
                    )

        # --- placement: client and eval arrays, store/streamer; under a mesh
        # also the (restored) state and parameters ------------------------------
        tracer.section("setup/data")
        mesh = None
        store = None
        streamer = None
        startup_stream = {"rec": None}  # stream_full's one-shot upload record
        if config.mesh_devices and config.mesh_devices > 1:
            mesh = mh_mesh if mh_mesh is not None else make_mesh(
                config.mesh_devices
            )
            # The DEVICE-resident client-axis length must split evenly over
            # the mesh: the whole population when resident (or full-cohort
            # streamed — the startup upload IS population-shaped), but only
            # the sampled COHORT under streamed sampling, where the cohort
            # slice is the array that carries PartitionSpec("clients").
            shard_len = cohort_n if stream_sampled else n_clients
            if shard_len % config.mesh_devices != 0:
                what = (
                    "cohort size (participation_fraction x worker_number)"
                    if stream_sampled else "worker_number"
                )
                raise ValueError(
                    f"{what} ({shard_len}) must be a multiple of "
                    f"mesh_devices ({config.mesh_devices})"
                )
        if streamed:
            # Host shard store owns the full-N arrays (data/residency.py);
            # the streamer owns their device side (parallel/streaming.py) —
            # under a mesh it uploads each cohort slice directly into the
            # client-axis PartitionSpec layout. config.validate() already
            # refused multihost + threaded.
            # Dynamic populations mutate label rows in place (drift) and the
            # store normally ALIASES the caller's packed arrays
            # (ascontiguousarray is zero-copy on contiguous input) — take
            # ownership of the label array up front so a caller-shared
            # client_data (bench legs, library callers) is never corrupted
            # as a side effect. Labels only: x/mask/sizes are never mutated
            # (growth appends into separate backing buffers).
            _pop_y = (
                np.array(client_data.y, copy=True) if pop is not None
                else client_data.y
            )
            if mh:
                from distributed_learning_simulator_tpu.data.residency import (
                    DistributedShardStore,
                )
                from distributed_learning_simulator_tpu.parallel.streaming import (
                    DistributedCohortStreamer,
                )

                # Owner-sharded store: this process keeps ONLY its owned
                # client slice (constructor copies it out of the full-N
                # view every process derives from the deterministic
                # partition); the streamer serves those members straight
                # into this host's addressable shards of the client-axis
                # PartitionSpec. config.validate() pinned the composition
                # (hashed sampler for sampled cohorts, no dynamic
                # population / client_stats / valuation / async).
                store = DistributedShardStore(
                    client_data.x, _pop_y, client_data.mask,
                    client_data.sizes,
                    state=client_state if stream_sampled else None,
                    host_id=jax.process_index(),
                    owner_bounds=mh_owner_bounds,
                )
                streamer = DistributedCohortStreamer(
                    store, algorithm, n_clients, mh_mesh, mh_block_bounds
                )
                if stream_full:
                    (cx, cy, cmask, _szs, _full_idx), startup_stream["rec"] = (
                        streamer.upload_full()
                    )
                    # sizes stays a host value: the mesh block below
                    # replicates it like the resident multihost path (a
                    # host array is placeable into a global sharding; the
                    # upload's client-sharded sizes array is not
                    # re-placeable cross-process).
                    sizes = client_data.sizes
                else:
                    cx = cy = cmask = None
                    sizes = client_data.sizes
                    client_state = None
                    logger.info(
                        "distributed shard store: host %d/%d owns %d of %d "
                        "clients (%.2f GB shard), cohort %d per dispatch",
                        store.host_id, store.n_hosts, store.n_owned,
                        n_clients, store.data_bytes() / 2**30, cohort_n,
                    )
            elif pop is not None and resumed_population is not None:
                # Resume mid-growth: the store starts at the startup
                # population (re-derived from the dataset partition), the
                # registration state grows it by the checkpointed joined
                # shards, and the (possibly grown) per-client state attaches
                # afterwards — lengths then agree by construction.
                store = HostShardStore(
                    client_data.x, _pop_y, client_data.mask,
                    client_data.sizes, state=None,
                )
                pop.restore(resumed_population, store)
                if stream_sampled and client_state is not None:
                    store.attach_state(client_state)
                logger.info(
                    "population resumed at cursor %d: %d registered, %d "
                    "alive (%d joined, %d departed)",
                    pop.cursor, pop.n_registered, int(pop.alive.sum()),
                    pop.totals["joins"], pop.totals["departs"],
                )
            else:
                store = HostShardStore(
                    client_data.x, _pop_y, client_data.mask,
                    client_data.sizes,
                    state=client_state if stream_sampled else None,
                )
            if not mh:
                streamer = CohortStreamer(store, algorithm, n_clients,
                                          mesh=mesh)
                if stream_full:
                    (cx, cy, cmask, sizes, _full_idx), startup_stream["rec"] = (
                        streamer.upload_full()
                    )
                    if client_state is not None:
                        # Full-cohort state lives on device across rounds
                        # exactly like resident (the whole population IS the
                        # cohort); it is a donated round_jit operand, so
                        # copy on placement.
                        client_state = _owned_device_tree(client_state)
                else:
                    # Sampled regime: no full-N device arrays exist; the
                    # cohort slices are per-dispatch operands. The loop's
                    # client_state stays None — the store owns the state
                    # between dispatches.
                    cx = cy = cmask = None
                    sizes = jnp.asarray(client_data.sizes)
                    client_state = None
                    logger.info(
                        "client_residency='streamed': %d clients "
                        "host-resident (%.2f GB), cohort %d per dispatch",
                        n_clients, store.data_bytes() / 2**30, cohort_n,
                    )
        # Host arrays go straight into their final layout: under a mesh each
        # device receives only its own client shard (and its replica of the
        # eval set) from host memory — the population is never staged whole
        # on device 0 and re-placed from there.
        if not streamed:
            host_data = (client_data.x, client_data.y, client_data.mask)
            if mesh is None:
                data_arrays = tuple(jnp.asarray(a) for a in host_data)
                sizes = jnp.asarray(client_data.sizes)
            else:
                data_arrays = shard_client_data(host_data, mesh)
                sizes = client_data.sizes
        if mesh is None:
            eval_batches = tuple(jnp.asarray(a) for a in eval_batches_np)
        else:
            # stream_full's population arrays were already uploaded sharded
            # by the streamer; stream_sampled has no full-N device arrays.
            # Persistent client state (resident or full-cohort streamed) is
            # client-axis sharded like the data; stream_sampled's state is
            # None here (the host store owns it — the per-round cohort
            # gather is sharded at dispatch time in the round loop).
            client_state = shard_client_data(client_state, mesh)
            global_params = replicate(global_params, mesh)
            if server_state is not None:
                server_state = replicate(server_state, mesh)
            if async_state is not None:
                # Replicated like the global model: the buffer is server-side
                # state, and the late-row reduction over the sharded client
                # axis resolves to the same replicated tree on every device.
                async_state = replicate(async_state, mesh)
            sizes = replicate(sizes, mesh)
            eval_batches = replicate(eval_batches_np, mesh)
            logger.info("client axis sharded over %d devices", config.mesh_devices)
            if not streamed:
                logger.info(
                    "client data shards: %s",
                    ", ".join(
                        f"clients {sh.index[0].start}-{sh.index[0].stop - 1} "
                        f"on {sh.device}"
                        for sh in data_arrays[0].addressable_shards
                    ),
                )
        if not streamed:
            cx, cy, cmask = data_arrays

        # --- round loop: its telemetry objects and closures first ----------------
        tracer.section("setup/build")
        history: list[dict] = []
        metrics_path = None
        if log_dir:
            metrics_path = os.path.join(log_dir, "metrics.jsonl")

        # Pipelined mode defers each round's device->host metric fetch until the
        # NEXT round has been dispatched, so the device->host transfer latency
        # overlaps device compute. Results
        # are bit-identical to the synchronous path — only fetch timing moves.
        # Not used when post_round must see metrics in the same round (Shapley),
        # nor when checkpointing needs per-client or server-optimizer state (those
        # buffers are donated to round r+1's dispatch before round r's deferred
        # checkpoint would read them).
        # Sharded checkpoints (distributed shard store): EVERY process
        # writes its own shard — only the manifest commit (and the legacy
        # single-file path) stays primary-only — so the flag must agree
        # across hosts (it also feeds the pipelining decision, which under
        # SPMD must resolve identically on every process).
        checkpointing = bool(
            config.checkpoint_dir and config.checkpoint_every
            and (is_primary or mh)
        )
        # Streamed residency with persistent per-client state: the per-round
        # writeback (a device_get of the cohort state) already syncs every
        # round, so a deferred metric fetch hides nothing — and a deferred
        # finalize would checkpoint the LIVE host store after the next
        # round's writeback mutated it.
        stream_stateful = (
            stream_sampled and store is not None and store.state is not None
        )
        pipelined = (
            config.pipeline_rounds
            and not stream_stateful
            and pop is None
            and algorithm.supports_round_pipelining
            and not (
                checkpointing
                and (client_state is not None or server_state is not None)
            )
        )
        if config.pipeline_rounds and not pipelined:
            # The user asked for pipelining; say out loud why it is off (each
            # deferred fetch otherwise silently costs a full host-link RTT).
            if pop is not None:
                reason = (
                    "population='dynamic' registration events mutate host "
                    "population state at every round boundary; a deferred "
                    "finalize would checkpoint the wrong stream cursor"
                )
            elif stream_stateful:
                reason = (
                    "streamed residency's per-round state writeback already "
                    "syncs with the dispatch (nothing left to hide)"
                )
            elif not algorithm.supports_round_pipelining:
                reason = "the algorithm's post_round must see each round's metrics"
            else:
                reason = (
                    "checkpointing needs per-client/server-optimizer state "
                    "that round r+1's dispatch would donate away"
                )
            logger.info("pipeline_rounds disabled: %s", reason)
        t_start = time.perf_counter()
        t_prev_done = t_start
        pending: dict | None = None
        # Robustness telemetry (docs/ROBUSTNESS.md): per-round survivor counts
        # and quorum rejections, accumulated for the result dict so callers
        # (and bench.py) can't silently trade robustness for speed.
        telemetry = {
            "rounds_rejected": 0,
            "survivor_counts": [],
            # Async federation (robustness/arrivals.py): simulated-clock sums
            # (async vs the wait-for-everyone counterfactual) and the
            # buffer-occupancy trail — the result dict's async_speedup_ratio.
            "sim_async_s": 0.0,
            "sim_sync_s": 0.0,
            "buffer_occupancy": [],
        }
        # Run telemetry (telemetry/; docs/OBSERVABILITY.md): phase timing,
        # recompile counting, HBM watermark. At the default 'off' both hooks
        # are inert and the metrics records stay in the legacy v1 layout.
        # (The records' phase_seconds are ``tracer.phases``: the tracer's
        # spans feed it through ``phase=``; carve/take/enabled read it.
        # The monitor is the tracer's: its listener is on since the first
        # line, its counting starts at the round loop, below.)
        recompile = tracer.monitor if tracer.phases.enabled else None
        post_warmup_compiles = {"count": 0} if recompile is not None else None
        # Distributed tracing (span_trace='on'): the tracer gains its
        # per-host journal (eager open lines, flight recorder) and the
        # multihost seams get it too. ``span_recorder`` is the tracer under
        # that name, None otherwise: the streamer, the checkpoint writers
        # and the records' v12 ``spans`` sub-object are gated on it — the
        # exact pre-feature program and records at the default 'off'.
        span_recorder = tracer if tracer.journal else None
        if span_recorder is not None:
            span_journal_dir = config.span_dir or log_dir
            if span_journal_dir:
                logger.info(
                    "span journal: %s (clock offset %+.6fs ± %.6fs vs host 0)",
                    span_recorder.attach(
                        span_journal_dir, span_clock_offset, span_clock_unc
                    ),
                    span_clock_offset, span_clock_unc,
                )
            else:
                # Non-primary hosts have no artifacts dir; without span_dir
                # the ring still works as a pure in-memory flight recorder,
                # but nothing persists — say so rather than silently drop.
                logger.warning(
                    "span_trace='on' but this host has no artifacts dir and "
                    "no span_dir; span journal disabled (in-memory flight "
                    "recorder only) — set span_dir to a shared directory"
                )
            if streamer is not None:
                streamer.span_recorder = span_recorder
                streamer.clock_offset_s = span_clock_offset
        # Per-client statistics (telemetry/client_stats.py): the round program
        # computes the [N, S] stats matrix in-program when on; the host fetches
        # it on the client_stats_every cadence inside the round's single metric
        # device_get, runs the median/MAD detector, and folds the result into
        # the schema-v3 record. None at the default 'off'.
        client_stats_cfg = ClientStats.from_config(config)
        telemetry["clients_flagged"] = 0
        # Dynamic population (robustness/population.py): rounds rejected by
        # the quorum policy where the round ALSO lost cohort members to
        # departures — the churn-collision telemetry the records flag as
        # rejected_by_churn.
        telemetry["churn_rejected"] = 0
        # One-row per-client state proto for joiners (stateful streamed
        # runs: reset_client_optimizer=False): replicated per joined client
        # by PopulationModel.apply. None for the stateless default.
        pop_state_proto = None
        if pop is not None and store is not None and store.state is not None:
            pop_state_proto = _host_client_state(
                algorithm, optimizer, global_params, 1
            )
        # Always-on client valuation (telemetry/valuation.py): the round
        # program emits a per-cohort streaming score vector (riding the
        # client-stats machinery); the host scales it by the server
        # loss-delta and folds it into the persistent exponentially-decayed
        # per-client valuation vector — a host numpy [N] array (attached to
        # the streamed host store when one exists, so the store stays the
        # one owner of full-N arrays), scatter-updated per cohort and
        # checkpointed in algo_state. On the sparse valuation_audit_every
        # cadence the auditor cross-validates the vector against a truncated
        # GTG walk over the round's exact re-materialized uploads. None at
        # the default 'off' — records stay at schema v6 or below.
        valuation_cfg = ClientValuation.from_config(config)
        vstate = None
        auditor = None
        telemetry["valuation_last_audit"] = None
        if valuation_cfg is not None:
            # Population-indexed: sized by the (possibly resumed-grown)
            # store under streamed residency so valued ids stay TRUE indices
            # across dynamic-population growth; the vector keeps growing
            # with the store (HostShardStore.grow appends zeros).
            vstate = ValuationState(
                store.n_clients if store is not None else n_clients,
                store=store,
            )
            if resumed_valuation is not None:
                vstate.load(resumed_valuation)
            elif start_round > 0:
                logger.warning(
                    "checkpoint carries no valuation vector (written before "
                    "the feature or with client_valuation='off'); valuation "
                    "restarts from zero"
                )
            if valuation_cfg.audit_every > 0:
                auditor = ValuationAuditor(
                    config, valuation_cfg, algorithm, model.apply, optimizer,
                    preprocess,
                    make_eval_fn(model.apply, preprocess=eval_preprocess),
                    client_data, eval_batches, n_clients,
                )
        # Where nothing reads the model a round started from once the round
        # is dispatched, the global model is donated with the client state:
        # the program writes the new global into the old one's buffer, and
        # two f32 copies of the model are alive on the device for three. A
        # pipelined loop keeps round r's global for its deferred evaluation
        # and finalize, and the Shapley servers' post_round, the valuation
        # auditor and the server optimizer each take the previous global:
        # those keep (1,).
        donate_global = (
            not pipelined and auditor is None
            and server_update_jit is None
            and algorithm.supports_global_donation
        )
        if donate_global and start_round == 0:
            # init_params' tree (a resumed one is owned already).
            global_params = _owned_device_tree(global_params)
        round_jit = jax.jit(
            round_fn, donate_argnums=(0, 1) if donate_global else (1,)
        )
        tracer.set_counter("global_donated", int(donate_global))
        # Predictive cost model (telemetry/costmodel.py): parse the reference
        # trace ONCE at startup (pure host-side gzip read); the roofline
        # prediction attaches to the run's LAST metrics record (schema v6)
        # with this run's measured steady round time as the anchor. None at
        # the default cost_model_trace=None — records stay at v5 or below.
        cost_ledger = None
        if config.cost_model_trace:
            cost_ledger = categorize_ops(config.cost_model_trace)
            if not cost_ledger or ledger_totals(cost_ledger)["bytes_gb"] <= 0:
                # Same degrade rule as bench.py's costmodel leg: CPU traces
                # carry no raw_bytes_accessed, and a zero-byte ledger
                # predicts nothing — warn, never fabricate a $0 record.
                logger.warning(
                    "cost_model_trace %r holds no byte-annotated device-op "
                    "events; cost model disabled for this run",
                    config.cost_model_trace,
                )
                cost_ledger = None
        telemetry["costmodel"] = None

        def _save_sharded_checkpoint(round_idx, new_global, client_state_rows,
                                     algo_state, rng_key) -> None:
            """Per-host checkpoint shards + manifest (distributed shard
            store; utils/checkpoint.py). EVERY process writes its shard —
            its owned per-client state slice plus the replicated global
            state, so each shard restores its process without cross-host
            reads — then all processes barrier on the round (the shard
            allgather doubles as the agreement check) and process 0 commits
            the round by writing the manifest. A host that dies between its
            shard write and the barrier leaves the round manifest-less:
            resume falls back one checkpoint interval, the torn-write
            discipline at shard granularity."""
            from jax.experimental import multihost_utils

            from distributed_learning_simulator_tpu.parallel.multihost import (
                allgather_wall_stamps,
            )
            from distributed_learning_simulator_tpu.utils.checkpoint import (
                gc_sharded_checkpoints,
                save_shard_checkpoint,
                shard_checkpoint_path,
                write_manifest,
            )

            pid = jax.process_index()
            save_shard_checkpoint(
                config.checkpoint_dir, round_idx, pid, n_procs,
                {
                    "global_params": jax.device_get(new_global),
                    "client_state": (
                        None if client_state_rows is None
                        else jax.tree_util.tree_map(
                            np.asarray, client_state_rows
                        )
                    ),
                    "algo_state": algo_state,
                    "rng_key": jax.device_get(
                        jax.random.key_data(rng_key)
                    ),
                },
                span_recorder=span_recorder,
            )
            if span_recorder is not None:
                # Checkpoint-barrier skew: a tiny aligned-arrival allgather
                # ahead of the agreement barrier — its wall is dominated by
                # the slowest host's shard write, and the gathered stamps
                # are the round's measured ckpt_skew_ms. Flight-recorder
                # eager: a host stuck here during a peer's death leaves its
                # open-line on disk. The skew is parked as pending (this
                # round's record already shipped) and rides the next one.
                wid = span_recorder.begin(
                    "ckpt_barrier_wait", "dcn_wait", round_idx=round_idx,
                    eager=True,
                )
                stamps = allgather_wall_stamps(
                    clock.wall() - span_clock_offset
                )
                skew_ms = float(stamps.max() - stamps.min()) * 1e3
                span_recorder.end(wid, skew_ms=round(skew_ms, 3))
                span_recorder.note_pending_skew("ckpt_skew_ms", skew_ms)
            agreed = multihost_utils.process_allgather(
                np.asarray([round_idx], dtype=np.int64)
            )
            if not (agreed == round_idx).all():
                rounds_seen = agreed.ravel().tolist()
                raise RuntimeError(
                    "sharded checkpoint barrier disagreement: processes "
                    f"are checkpointing different rounds ({rounds_seen}) — "
                    "SPMD round sequencing diverged"
                )
            if is_primary:
                write_manifest(
                    config.checkpoint_dir, round_idx,
                    {
                        "n_hosts": n_procs,
                        "n_clients": n_clients,
                        "owner_bounds": [int(b) for b in mh_owner_bounds],
                        "cohort": cohort_n,
                        "mesh_devices": int(config.mesh_devices),
                        "shards": [
                            os.path.basename(shard_checkpoint_path(
                                config.checkpoint_dir, round_idx, h, n_procs
                            ))
                            for h in range(n_procs)
                        ],
                    },
                    span_recorder=span_recorder,
                )
                gc_sharded_checkpoints(
                    config.checkpoint_dir, config.checkpoint_keep_last
                )

        def emit_record(round_idx, metrics, fetched_loss, fetched_tel, ctx,
                        tel_rec_fn, stream_rec=None,
                        audit_fn=None, population_rec=None,
                        multihost_rec=None):
            """Build + persist ONE round's metrics record from already-fetched
            host values: post_round hook, then, under the ``record`` span,
            record assembly, quorum/cohort telemetry accumulation, client-stats
            detection, history append + metrics.jsonl line. ``tel_rec_fn``
            builds the telemetry sub-object lazily AFTER post_round (so
            host-side compiles attribute to this round)."""
            nonlocal prev_metrics, t_prev_done
            with tracer.span("post_round", "phase", round_idx=round_idx,
                             phase="post_round"):
                extra = algorithm.post_round(ctx) or {}
            # Mesh-sharded GTG walk provenance (algorithms/shapley.py): a
            # ``gtg`` dict in the post_round extras is the schema-v10
            # sub-object — routed through the shared record builder below
            # (lowest-version stamping), never inlined into the v1 base.
            gtg_rec = extra.pop("gtg", None)
            # The round is complete HERE: ``now`` ends its round_seconds.
            now = time.perf_counter()
            tracer.round_done(round_idx, now)
            with tracer.span("record", "host", round_idx=round_idx):
                # Wall time between successive round completions: covers train +
                # eval + metric fetch + host post_round (Shapley time included —
                # it IS per-round server work). Sums to total wall time, less
                # the periodic checkpoints of a loop that is not pipelined
                # (``_finalize`` restarts the clock after one).
                record = build_base_round_record(
                    config, round_idx, metrics, fetched_loss, fetched_tel, extra,
                    round_seconds=now - t_prev_done,
                )
                if "survivor_count" in record:
                    telemetry["survivor_counts"].append(record["survivor_count"])
                if record.get("round_rejected"):
                    telemetry["rounds_rejected"] += 1
                    logger.warning(
                        "round %d REJECTED by quorum policy (survivors=%s, "
                        "min_survivors=%d): previous global model retained",
                        round_idx, record.get("survivor_count"),
                        config.min_survivors,
                    )
                    if span_recorder is not None:
                        # Flight-recorder trigger: a quorum rejection is a
                        # fault event — snapshot what every subsystem was doing
                        # around it into the journal for the postmortem.
                        span_recorder.flush_inflight("quorum_rejected")
                t_prev_done = now
                cs_rec = None
                extras = {
                    k: float(fetched_tel[k])
                    for k in ("quant_mse", "vote_agreement")
                    if k in fetched_tel
                }
                if "client_stats" in fetched_tel:
                    cs_rec, n_flagged = detect_and_record(
                        fetched_tel["client_stats"], client_stats_cfg,
                        round_idx, logger=logger,
                        participants=fetched_tel.get("participants"),
                        extras=extras,
                    )
                    telemetry["clients_flagged"] += n_flagged
                elif extras:
                    # Algorithms without per-client deltas (sign_SGD) report
                    # round scalars only; non-finite values become null like
                    # every other client-stats field (strict-JSON contract).
                    cs_rec = {
                        "n_clients": n_clients,
                        **{
                            k: (v if np.isfinite(v) else None)
                            for k, v in extras.items()
                        },
                    }
                async_rec = None
                if "sim_duration" in fetched_tel:
                    # Deadline-round outcome (robustness/arrivals.py): the v4
                    # ``async`` sub-object. mean_staleness is meaningful only
                    # over a non-empty late batch (null keeps strict JSON).
                    n_late_rec = int(fetched_tel["late_count"])
                    async_rec = {
                        "on_time": int(fetched_tel["on_time_count"]),
                        "late": n_late_rec,
                        "buffer": int(fetched_tel["buffer_count"]),
                        "applied": bool(fetched_tel["buffer_applied"]),
                        "mean_staleness": (
                            round(float(fetched_tel["mean_staleness"]), 4)
                            if n_late_rec else None
                        ),
                        "sim_round_s": round(float(fetched_tel["sim_duration"]), 6),
                        "sim_round_sync_s": round(
                            float(fetched_tel["sim_duration_sync"]), 6
                        ),
                        "sim_clock_s": round(float(fetched_tel["sim_clock"]), 6),
                    }
                    telemetry["sim_async_s"] += float(fetched_tel["sim_duration"])
                    telemetry["sim_sync_s"] += float(
                        fetched_tel["sim_duration_sync"]
                    )
                    telemetry["buffer_occupancy"].append(
                        int(fetched_tel["buffer_count"])
                    )
                val_rec = None
                if vstate is not None and "valuation_scores" in fetched_tel:
                    # Streaming valuation fold (telemetry/valuation.py): the
                    # round's in-program scores, scaled by the server loss-delta
                    # (previous test loss minus this round's — post_round has
                    # NOT yet replaced prev_metrics at this point, so the delta
                    # is exactly this round's improvement), scatter-folded into
                    # the persistent per-client vector. Round 0 (no previous
                    # metric) folds a 0 delta — the vector starts moving once
                    # there is a baseline to improve on.
                    v_ids = fetched_tel.get("participants")
                    if v_ids is not None:
                        v_ids = np.asarray(v_ids)
                    loss_delta = (
                        float(prev_metrics["loss"]) - float(metrics["loss"])
                        if prev_metrics else 0.0
                    )
                    vstate.fold(
                        v_ids, np.asarray(fetched_tel["valuation_scores"]),
                        loss_delta, valuation_cfg.decay,
                    )
                    audit_rec = audit_fn(v_ids) if audit_fn is not None else None
                    if audit_rec is not None:
                        telemetry["valuation_last_audit"] = {
                            "round": round_idx, **audit_rec,
                        }
                        logger.info(
                            "round %d valuation audit: spearman=%s pearson=%s "
                            "(%d permutations, %d subset evals, converged=%s, "
                            "memo_hit_rate=%s, %.1fs)",
                            round_idx, audit_rec["spearman"],
                            audit_rec["pearson"], audit_rec["permutations"],
                            audit_rec["subset_evals"], audit_rec["converged"],
                            audit_rec["memo_hit_rate"], audit_rec["seconds"],
                        )
                    val_rec = valuation_record(
                        vstate, v_ids, loss_delta, audit=audit_rec,
                    )
                cm_rec = None
                if cost_ledger is not None and round_idx == config.round - 1:
                    # The run's measured per-round wall, averaged over the steady
                    # rounds (round 0 carries compile).
                    walls = [h["round_seconds"] for h in history] + [
                        record["round_seconds"]
                    ]
                    steady = walls[1:] or walls
                    cm_rec = costmodel_record(
                        cost_ledger,
                        trace_rounds=config.cost_model_trace_rounds,
                        anchor=config.cost_model_topology,
                        measured_ms=1e3 * sum(steady) / len(steady),
                        param_bytes=_f32_param_bytes(global_params),
                        run_rounds=config.round,
                    )
                    telemetry["costmodel"] = cm_rec
                pop_rec = None
                if population_rec is not None:
                    # The churn-collision flag needs the round's quorum verdict,
                    # known only here: rejected AND cohort members departed this
                    # round (robustness/population.py, the PR 2 contract's
                    # open-world face).
                    pop_rec = dict(population_rec)
                    pop_rec["rejected_by_churn"] = bool(
                        record.get("round_rejected")
                        and pop_rec.get("cohort_departs", 0) > 0
                    )
                    if pop_rec["rejected_by_churn"]:
                        telemetry["churn_rejected"] += 1
                tel_rec = tel_rec_fn()
                spans_rec = None
                if span_recorder is not None:
                    # Pop the round's span aggregate for the schema-v12
                    # sub-object, then drain completed spans to the journal —
                    # once per round, the only hot-path journal I/O.
                    spans_rec = span_recorder.round_summary(round_idx)
                    span_recorder.flush()
                if (
                    tel_rec is not None or cs_rec is not None
                    or async_rec is not None or stream_rec is not None
                    or cm_rec is not None or val_rec is not None
                    or pop_rec is not None or gtg_rec is not None
                    or multihost_rec is not None or spans_rec is not None
                ):
                    record = build_round_record(
                        record, tel_rec, cs_rec, async_rec, stream_rec, cm_rec,
                        val_rec, population=pop_rec, gtg=gtg_rec,
                        multihost=multihost_rec, spans=spans_rec,
                    )
                history.append(record)
                if metrics_path:
                    with open(metrics_path, "a") as f:
                        f.write(json.dumps(record) + "\n")
                logger.info(
                    "round %d: test_acc=%.4f test_loss=%.4f (%.2fs)",
                    round_idx, metrics["accuracy"], metrics["loss"],
                    record["round_seconds"],
                )
                prev_metrics = metrics

        def finalize(p: dict) -> None:
            # Flight-recorder envelope: an EAGER span (open-line journaled
            # before the body runs) covering metric fetch, record emission,
            # and the checkpoint block — the chaos harness's injected crash
            # (maybe_crash, last statement below) fires inside it, so a
            # SIGKILL'd host's journal names this span as its in-flight
            # postmortem without any cleanup code running.
            nonlocal t_prev_done
            with tracer.span(
                "finalize", "round", round_idx=p["round_idx"], eager=True,
            ):
                saved = _finalize(p)
            if saved and not pipelined:
                # The device idled while the host wrote (nothing is
                # dispatched behind a finalize that is not deferred):
                # those seconds are the save's, not the next round's,
                # whose clock starts here. Gigabytes take seconds.
                t_prev_done = time.perf_counter()
            # The loop keeps the entry bound until the next round's: let go
            # of the two models it names, or the round before last's stays
            # on the device through the next dispatch (a third f32 copy).
            p["prev_global"] = p["new_global"] = None

        def _finalize(p: dict) -> bool:
            """True where the round's periodic checkpoint was written."""
            saved = False
            tel_keys = [
                k for k in ("survivor_count", "round_rejected", "participants",
                            "model_counts")
                if k in p["aux"]
            ]
            # Client-stats fetch cadence (client_stats_every): the [N, S]
            # matrix and its round scalars ride the round's SINGLE metric
            # device_get below — no extra host sync, async dispatch preserved.
            cs_fetch = (
                client_stats_cfg is not None
                and client_stats_cfg.fetch_round(p["round_idx"])
            )
            cs_keys = [
                k for k in ("client_stats", "quant_mse", "vote_agreement")
                if k in p["aux"]
            ] if cs_fetch else []
            # Valuation scores ride EVERY round's single metric fetch (the
            # host fold needs each round's loss-delta pairing) — N floats,
            # not on the client_stats_every cadence.
            val_keys = (
                ["valuation_scores"]
                if vstate is not None and "valuation_scores" in p["aux"]
                else []
            )
            async_keys = [k for k in _ASYNC_AUX_KEYS if k in p["aux"]]
            with tracer.span("host_sync", "phase", round_idx=p["round_idx"],
                             phase="host_sync"), _oom_hint(
                    config, p["new_global"], n_clients,
                    site="deferred metric fetch"):
                fetched_metrics, fetched_loss, fetched_tel = jax.device_get(
                    (p["metrics_dev"], p["mean_loss_dev"],
                     {k: p["aux"][k]
                      for k in tel_keys + cs_keys + val_keys + async_keys})
                )
            metrics = {k: float(v) for k, v in fetched_metrics.items()}
            if "model_counts" in fetched_tel:
                tracer.add_counts(fetched_tel["model_counts"])
            if p.get("participants_host") is not None and (
                "participants" in fetched_tel
            ):
                # Distributed cohort assembly: the device operand carries the
                # OWNER-permuted cohort (row order = placement order); the
                # record's cohort_hash must stay comparable across
                # topologies, so substitute the host-replayed DRAW-order
                # cohort — same set, canonical order. Safe because the only
                # consumer left under multihost streamed is the hash
                # (client_stats/valuation are cause-named refusals there).
                fetched_tel["participants"] = p["participants_host"]
            ctx = RoundContext(
                round_idx=p["round_idx"],
                global_params=p["new_global"],
                prev_global_params=p["prev_global"],
                sizes=sizes,
                aux=p["aux"],
                metrics=metrics,
                prev_metrics=prev_metrics,
                eval_batches=eval_batches,
                log_dir=log_dir,
            )
            if "client_stats" in fetched_tel:
                # Hand post_round hooks (Shapley's attribution cross-check)
                # the ALREADY-fetched matrix so they never re-transfer the
                # device array the single metric device_get above carried.
                ctx.extra["client_stats_np"] = np.asarray(
                    fetched_tel["client_stats"]
                )

            def tel_rec_fn():
                if not tracer.phases.enabled:
                    return None
                # Attribute post_round/host-side compiles, then fold this
                # round's telemetry into a schema-v2/v3 record (shared
                # builder: utils/reporting.py). Warmup = the first EXECUTED
                # round (it legitimately compiles the round + eval programs);
                # anything later is the shape-instability warning.
                recompile.attribute(p["round_idx"])
                events = recompile.take(p["round_idx"])
                if span_recorder is not None:
                    # Recompile events become instant spans: on the stitched
                    # timeline a post-warmup compile shows up AT the host
                    # and round that paid for it.
                    for _fn_name, _secs in events:
                        span_recorder.event(
                            _fn_name, "compile", round_idx=p["round_idx"],
                            seconds=round(_secs, 6),
                        )
                n_compiles = log_round_compiles(
                    logger, p["round_idx"], events,
                    warmup=p["round_idx"] == start_round,
                )
                if p["round_idx"] > start_round:
                    post_warmup_compiles["count"] += n_compiles
                tel_rec = {
                    "phase_seconds": {
                        k: round(v, 6)
                        for k, v in sorted(
                            tracer.phases.take(p["round_idx"]).items()
                        )
                    },
                    "compiles": n_compiles,
                }
                if events:
                    tel_rec["compiled"] = [name for name, _ in events]
                peak = peak_hbm_bytes()
                if peak is not None:
                    tel_rec["peak_hbm_bytes"] = peak
                return tel_rec

            def audit_fn(v_ids):
                """Sparse-cadence GTG cross-validation (telemetry/valuation
                .py): replays THIS round's cohort from its round key against
                the pre-round global params — a pure read, the recorded
                aggregate came from the normal program."""
                if auditor is None or not auditor.due(p["round_idx"]):
                    return None
                with tracer.span("valuation_audit", "host",
                                 round_idx=p["round_idx"]):
                    return auditor.run(
                        p["round_idx"], p["round_key"], p["prev_global"],
                        v_ids, vstate.values,
                        lr_scale=float(
                            np.float32(_lr_factor(config, p["round_idx"]))
                        ),
                    )

            emit_record(
                p["round_idx"], metrics, fetched_loss, fetched_tel, ctx,
                tel_rec_fn, stream_rec=p.get("stream"), audit_fn=audit_fn,
                population_rec=p.get("population"),
                multihost_rec=p.get("multihost"),
            )

            if (
                checkpointing
                and (p["round_idx"] + 1) % config.checkpoint_every == 0
            ):
                with tracer.span("checkpoint", "host",
                                 round_idx=p["round_idx"]):
                    algo_state = _algo_checkpoint_state(
                        algorithm, metrics, p["server_state"],
                        p.get("async_state"),
                        vstate.values if vstate is not None else None,
                        # Population events for this round were applied
                        # before finalize (pipelining is off under dynamic),
                        # so the snapshot is exactly the state the NEXT
                        # round draws from.
                        pop.checkpoint_state(store) if pop is not None
                        else None,
                    )
                    if mh:
                        _save_sharded_checkpoint(
                            p["round_idx"], p["new_global"],
                            p["client_state"], algo_state, p["key"],
                        )
                    else:
                        save_checkpoint(
                            os.path.join(
                                config.checkpoint_dir,
                                f"round_{p['round_idx']}.ckpt"
                            ),
                            p["round_idx"], p["new_global"],
                            p["client_state"], algo_state, p["key"],
                        )
                        gc_checkpoints(config.checkpoint_dir,
                                       config.checkpoint_keep_last)
                saved = True
            # Chaos-harness hook (robustness/chaos.py): inert unless
            # DLS_CRASH_AT_ROUND is set. Placed after the checkpoint block so
            # an injected crash models "the process died right after round N
            # was persisted".
            maybe_crash(p["round_idx"])
            return saved

        profile_from = getattr(config, "profile_from_round", 0)
        # SIGTERM grace hook (TPU preemption notice, docs/ROBUSTNESS.md): the
        # handler only sets a flag; the round loop finishes the in-flight
        # round, flushes any deferred round, writes a final checkpoint, and
        # returns cleanly. Installed only in the main thread (signal.signal
        # raises elsewhere — e.g. the threaded test harness), and the previous
        # handler is restored on exit so library callers keep their own.
        preempt = {"flag": False}
        prev_sigterm = None
        sigterm_installed = False
        if threading.current_thread() is threading.main_thread():
            def _on_sigterm(signum, frame):
                preempt["flag"] = True

            try:
                prev_sigterm = signal.signal(signal.SIGTERM, _on_sigterm)
                sigterm_installed = True
            except ValueError:
                pass
        completed_round = start_round - 1
        preempted_at = None
        with ExitStack() as profile_stack:
            if recompile is not None:
                # Scoped to the round loop: the monitor owns process-global
                # logging state (jax_log_compiles + compile-logger capture),
                # restored on exit even if a round raises.
                profile_stack.enter_context(recompile)
            if config.profile_dir and profile_from <= start_round:
                profile_stack.enter_context(profile_session(config.profile_dir))
                profile_from = None  # entered
            tracer.section(None)  # set-up ends; the loop's spans are `round`s
            # try/finally: if a later round crashes (OOM, preemption, SIGINT),
            # the deferred round that already completed on device still gets its
            # metrics line and checkpoint written before unwinding.
            try:
                # Next round's host-replayed cohort (stream_sampled): the
                # prefetched upload this index list describes is already
                # in flight when the round that uses it starts.
                stream_next_idx = None
                for round_idx in range(start_round, config.round):
                    if (
                        config.profile_dir
                        and profile_from is not None
                        and round_idx >= profile_from
                    ):
                        # Deferred trace start (config.profile_from_round):
                        # keeps round 0's XLA compile and its host events
                        # out of the trace, so the captured window is
                        # steady-state rounds only
                        # (scripts/profile_sign_round.py's method). Earlier
                        # rounds were dispatched asynchronously: wait for
                        # the device to finish them, or their tail lands
                        # inside the window (v5e, PR 21: a "one-round"
                        # flagship trace held 3.6 s of device ops).
                        jax.block_until_ready(
                            (global_params, pending and pending["metrics_dev"])
                        )
                        profile_stack.enter_context(
                            profile_session(config.profile_dir)
                        )
                        profile_from = None
                    # One span per loop iteration: the iteration of round
                    # r dispatches r and, pipelined, finalizes r-1 (whose
                    # spans carry round r-1 under this parent).
                    with tracer.span("round", "iter", round_idx=round_idx):
                        key, round_key = jax.random.split(key)
                        if span_recorder is not None and streamer is not None:
                            # Skew/occupancy spans emitted inside the
                            # streamer (spill exchange, prefetch worker)
                            # attribute to the round being dispatched.
                            streamer.span_round = round_idx
                        with _oom_hint(config, global_params, n_clients):
                            # The schedule factor is a traced operand only when a
                            # schedule is active; the constant default uses the
                            # round_fn's Python default 1.0, which constant-folds
                            # at trace time (no per-step scale multiply in the
                            # compiled program).
                            lr_args = () if config.lr_schedule.lower() == (
                                "constant"
                            ) else (
                                jnp.float32(_lr_factor(config, round_idx)),
                            )
                            async_kw = (
                                {"async_state": async_state}
                                if async_ctl is not None else {}
                            )
                            stream_rec = None
                            pop_rec = None
                            mh_rec = None
                            mh_plan = None
                            if stream_sampled:
                                # Streamed dispatch: cohort slices arrive as
                                # pre-gathered operands (prefetched while the
                                # previous round computed); persistent state
                                # gathers from the host store (post the
                                # previous round's writeback) and scatters
                                # back after this dispatch.
                                pop_events = pop_words = dep_mask = None
                                if pop is not None:
                                    # Dynamic population: the cohort is
                                    # drawn from the PRE-event registered
                                    # index space (departed masked out of
                                    # the hashed stream); this round's
                                    # events come from the fold_in-decoupled
                                    # registration stream and APPLY after
                                    # the dispatch — a joiner is sampleable
                                    # from the next round, a departure that
                                    # hits this cohort rides the departed
                                    # operand. Drift levels advance before
                                    # the gather so sampled drifting
                                    # clients train on this round's labels.
                                    pop_words = pop_key_words(
                                        round_key, pop.seed
                                    )
                                    with tracer.span(
                                            "sample", "phase",
                                            round_idx=round_idx, phase="sample"):
                                        idx_np = streamer.cohort_for(
                                            round_key,
                                            n=pop.n_registered,
                                            alive=pop.alive,
                                            k=cohort_n,
                                        )
                                    pop_events = pop.draw_events(
                                        pop_words, round_idx
                                    )
                                    dep_mask = pop.cohort_departed_mask(
                                        pop_events, idx_np
                                    )
                                    pop.apply_drift(store, round_idx, idx_np)
                                elif stream_next_idx is not None:
                                    idx_np = stream_next_idx
                                else:
                                    # First round / resume: the draw is not
                                    # hidden behind a prior dispatch — its
                                    # own `sample` phase window (under the
                                    # distributed store this window also
                                    # covers the owner assembly + spill
                                    # exchange).
                                    with tracer.span(
                                            "sample", "phase",
                                            round_idx=round_idx, phase="sample"):
                                        idx_np = streamer.cohort_for(
                                            round_key
                                        )
                                        if mh:
                                            idx_np = streamer.plan(idx_np)
                                stream_next_idx = None
                                if mh:
                                    # Owner-sharded assembly: this host's
                                    # block rows, with ownership-imbalance
                                    # spill already exchanged at plan time;
                                    # the upload adds the draw_pos operand
                                    # that maps rows back to draw order.
                                    mh_plan = idx_np
                                    (
                                        (sx, sy, sm, ssz, sidx, sdpos),
                                        stream_rec, mh_plan,
                                    ) = streamer.acquire_plan(mh_plan)
                                    mh_kw = {"draw_pos": sdpos}
                                else:
                                    (sx, sy, sm, ssz, sidx), stream_rec = (
                                        streamer.acquire([idx_np])
                                    )
                                    mh_kw = {}
                                state_k = None
                                if store.state is not None:
                                    if mh:
                                        # Owner-assembled block state (own
                                        # rows local, spill rows exchanged),
                                        # placed straight into the
                                        # client-axis layout.
                                        state_k = streamer.gather_state_device(
                                            mh_plan
                                        )
                                    else:
                                        # Donated operand: owned buffers,
                                        # not a zero-copy view of the numpy
                                        # gather.
                                        state_k = _owned_device_tree(
                                            algorithm.gather_client_state(
                                                store, idx_np
                                            )
                                        )
                                        if mesh is not None:
                                            # Cohort state joins the cohort
                                            # slice's client-axis layout.
                                            state_k = shard_client_data(
                                                state_k, mesh
                                            )
                                dyn_kw = (
                                    {"departed": jnp.asarray(dep_mask)}
                                    if pop is not None else {}
                                )
                                with tracer.span(
                                        "dispatch", "phase", round_idx=round_idx,
                                        phase="client_step") as _ph:
                                    new_global, new_state_k, aux = round_jit(
                                        global_params, state_k, sx, sy, sm,
                                        ssz, sidx, round_key,
                                        *lr_args, **async_kw, **dyn_kw,
                                        **mh_kw,
                                    )
                                    # Prefetch the next round's cohort while
                                    # this dispatch computes (the upload runs
                                    # on the streamer's worker thread). The
                                    # draw deliberately overlaps device
                                    # compute; its host cost is carved out
                                    # of this client_step window into the
                                    # `sample` phase so the ~1 s exact
                                    # replay at N=1e6 stays visible.
                                    # Dynamic populations draw synchronously
                                    # instead: the next cohort depends on
                                    # this round's registration events
                                    # (applied below), and the O(cohort)
                                    # hashed draw is microseconds.
                                    if pop is None and (
                                        round_idx + 1 < config.round
                                    ) and not preempt["flag"]:
                                        _, _nxt_rk = jax.random.split(key)
                                        if mh:
                                            # Plan (incl. the collective
                                            # spill exchange) on the MAIN
                                            # thread at the same loop point
                                            # on every host — collective
                                            # launch order stays identical
                                            # across processes; only the
                                            # device_put assembly rides the
                                            # worker thread.
                                            _t_s = clock.monotonic()
                                            stream_next_idx = streamer.plan(
                                                streamer.cohort_for(_nxt_rk)
                                            )
                                            tracer.phases.carve(
                                                round_idx, "sample",
                                                clock.monotonic() - _t_s,
                                                "client_step",
                                            )
                                            streamer.prefetch_plan(
                                                stream_next_idx
                                            )
                                        else:
                                            stream_next_idx = (
                                                streamer.cohort_for(_nxt_rk)
                                            )
                                            tracer.phases.carve(
                                                round_idx, "sample",
                                                streamer.last_sample_seconds,
                                                "client_step",
                                            )
                                            streamer.prefetch(
                                                [stream_next_idx]
                                            )
                                    _ph.fence((new_global, aux))
                                # Host store is the source of truth between
                                # dispatches: checkpoint/resume read it.
                                streamer.writeback(
                                    mh_plan if mh else idx_np, new_state_k,
                                    stream_rec,
                                )
                                if mh:
                                    mh_rec = streamer.multihost_record(
                                        mh_plan, stream_rec or {}
                                    )
                                if pop is not None:
                                    # Registration events apply at the round
                                    # boundary, after the writeback and
                                    # before this round's checkpoint: the
                                    # persisted state is exactly what the
                                    # next round's draw sees.
                                    pop.apply(
                                        pop_events, store,
                                        state_proto=pop_state_proto,
                                        words=pop_words,
                                    )
                                    pop_rec = pop.round_record(
                                        pop_events,
                                        int(np.count_nonzero(dep_mask)),
                                    )
                            else:
                                if (
                                    stream_full
                                    and startup_stream["rec"] is not None
                                ):
                                    # One-shot population upload: recorded on
                                    # the first round's record.
                                    stream_rec = startup_stream["rec"]
                                    startup_stream["rec"] = None
                                if mh:
                                    # Full-cohort distributed upload: shard
                                    # provenance on every round's record
                                    # (spill is structurally zero — owner
                                    # bounds ARE the device blocks).
                                    mh_rec = streamer.multihost_record(
                                        None, stream_rec or {}
                                    )
                                with tracer.span(
                                        "dispatch", "phase", round_idx=round_idx,
                                        phase="client_step") as _ph:
                                    new_global, client_state, aux = round_jit(
                                        global_params, client_state, cx, cy,
                                        cmask, sizes,
                                        round_key, *lr_args, **async_kw,
                                    )
                                    _ph.fence((new_global, aux))
                            if async_ctl is not None:
                                # Pop the buffer carry before any record/aux
                                # consumer sees it; it becomes the next
                                # round's async_state operand.
                                aux = dict(aux)
                                async_state = aux.pop("async_state")
                            if server_update_jit is not None:
                                # When the round program carries a quorum verdict,
                                # the server optimizer must see it: a rejected
                                # round freezes the optimizer state and leaves the
                                # params untouched (momentum alone would otherwise
                                # move the "retained" model).
                                srv_args = (global_params, new_global, server_state)
                                if "round_rejected" in aux:
                                    srv_args += (aux["round_rejected"],)
                                with tracer.span(
                                        "aggregate", "phase", round_idx=round_idx,
                                        phase="aggregate") as _ph:
                                    new_global, server_state = server_update_jit(
                                        *srv_args
                                    )
                                    _ph.fence(new_global)
                        with tracer.span(
                            "eval_dispatch", "phase", round_idx=round_idx,
                            phase="eval",
                        ) as _ph, _oom_hint(
                            config, new_global, n_clients, site="eval"
                        ):
                            metrics_dev = evaluate(new_global, *eval_batches)
                            _ph.fence(metrics_dev)
                        if recompile is not None:
                            # Compiles are synchronous with trace/lower, so events
                            # pending here came from this round's dispatches
                            # (under pipelining, the deferred finalize of round
                            # r-1 runs after this and must not absorb them).
                            recompile.attribute(round_idx)
                        entry = {
                            "round_idx": round_idx,
                            "round_key": round_key,
                            "new_global": new_global,
                            # A donated global is gone: its buffer
                            # holds new_global.
                            "prev_global": (
                                None if donate_global else global_params
                            ),
                            # Sampled streamed: the (post-writeback) host
                            # store is what a checkpoint must persist.
                            "client_state": (
                                store.state if stream_sampled
                                else None if pipelined else client_state
                            ),
                            "aux": aux,
                            "metrics_dev": metrics_dev,
                            "mean_loss_dev": aux.get("mean_client_loss", np.nan),
                            "key": key,
                            "server_state": server_state,
                            "async_state": async_state,
                            "stream": stream_rec,
                            "population": pop_rec,
                            "multihost": mh_rec,
                            # Draw-order cohort for the record's cohort_hash
                            # (the device operand is owner-permuted under
                            # the distributed layout).
                            "participants_host": (
                                mh_plan.idx if mh_plan is not None else None
                            ),
                        }
                        global_params = new_global
                        if pipelined:
                            # Take ownership of `entry` before finalizing the prior
                            # round: if that finalize raises, the finally block still
                            # records this round (the raising round is what's lost).
                            prev_pending, pending = pending, entry
                            if prev_pending is not None:
                                finalize(prev_pending)
                        else:
                            finalize(entry)
                    completed_round = round_idx
                    if preempt["flag"]:
                        # Finish-in-flight semantics: this round completed (and
                        # with pipelining its deferred finalize runs in the
                        # crash-flush below); no new round is dispatched.
                        break
            except BaseException as crash_exc:
                # Flight recorder (telemetry/spans.py): an unhandled crash
                # force-flushes the last-K spans plus every still-open span
                # with its `inflight` marker — the journal then names
                # exactly what this host was doing when the run died (a
                # peer's SIGKILL surfacing as a broken collective lands
                # here too). Best-effort by construction: flush_inflight
                # never raises past its own I/O, and the original exception
                # always propagates.
                if span_recorder is not None:
                    try:
                        span_recorder.flush_inflight(
                            type(crash_exc).__name__
                        )
                    except Exception:
                        pass
                raise
            finally:
                if sigterm_installed:
                    signal.signal(signal.SIGTERM, prev_sigterm)
                if streamer is not None:
                    # Join the worker thread (an in-flight prefetch must not
                    # outlive the run) — the store keeps its state for the
                    # checkpoint/result paths below.
                    streamer.close()
                if pending is not None:
                    # Crash-flush of the last deferred round. Best-effort: if
                    # finalize itself is what failed in-loop (full disk, post_round
                    # bug), don't let a second failure here supersede the original
                    # exception in the propagated traceback.
                    try:
                        finalize(pending)
                    except Exception:
                        logger.exception(
                            "failed to record round %d during unwind",
                            pending["round_idx"],
                        )
                    finally:
                        pending = None

        tracer.section("teardown", "run")
        if preempt["flag"]:
            # Graceful preemption: the in-flight round finished and was
            # finalized above; persist it even off the checkpoint_every
            # cadence so the resumed run loses nothing, then exit cleanly.
            preempted_at = completed_round
            if span_recorder is not None:
                # Flight recorder: journal the preemption moment (last-K
                # spans + anything still open) so a postmortem can see what
                # the SIGTERM interrupted even though the exit is clean.
                span_recorder.flush_inflight("sigterm")
            if mh and config.checkpoint_dir:
                # No off-cadence force-write under the distributed store:
                # the sharded commit needs a cross-host barrier, and SIGTERM
                # delivery is per-process — a host whose peer never got the
                # signal would block in the barrier instead of exiting. The
                # checkpoint_every cadence (whose barrier every host
                # reaches by SPMD construction) is the durability contract.
                logger.warning(
                    "preempted at round %d (SIGTERM): sharded checkpoints "
                    "persist on the checkpoint_every cadence only (last "
                    "committed manifest is the resume point); exiting "
                    "cleanly", completed_round,
                )
            elif (
                config.checkpoint_dir and is_primary
                and completed_round >= start_round
            ):
                forced_path = os.path.join(
                    config.checkpoint_dir, f"round_{completed_round}.ckpt"
                )
                if not os.path.exists(forced_path):
                    save_checkpoint(
                        forced_path, completed_round, global_params,
                        store.state if stream_sampled else client_state,
                        _algo_checkpoint_state(
                            algorithm, prev_metrics, server_state, async_state,
                            vstate.values if vstate is not None else None,
                            pop.checkpoint_state(store) if pop is not None
                            else None,
                        ),
                        key,
                    )
                    gc_checkpoints(
                        config.checkpoint_dir, config.checkpoint_keep_last
                    )
                logger.warning(
                    "preempted at round %d (SIGTERM): final checkpoint %s "
                    "written; exiting cleanly — resume with config.resume=True",
                    completed_round, forced_path,
                )
            else:
                logger.warning(
                    "preempted at round %d (SIGTERM): no checkpoint_dir "
                    "configured, exiting cleanly without persisting",
                    completed_round,
                )

        span_summary = None
        if span_recorder is not None:
            # The run summary is what bench.py's mhost leg and scripts read
            # (run-total counts, seconds by category, and the worst barrier
            # skews seen); the journal is drained and closed once the root
            # span has ended (``tracer.finish()``, below).
            span_summary = span_recorder.run_summary()

        total = time.perf_counter() - t_start
        # len(history) counts THIS run's finalized rounds (a preempted run
        # completes fewer than config.round - start_round).
        n_rounds = len(history)
        logger.info(
            "finished %d rounds x %d clients in %.2fs (%.1f client-rounds/sec)",
            n_rounds, n_clients, total,
            n_rounds * n_clients / max(total, 1e-9),
        )
        if tracer.recording:
            # [before, after] the first round completed, in seconds.
            logger.info("tracing, lowering, compiles, host syncs, rounds: %s",
                        tracer.counters())
        return {
            "global_params": global_params,
            "client_state": store.state if stream_sampled else client_state,
            "history": history,
            "algorithm": algorithm,
            "final_accuracy": history[-1]["test_accuracy"] if history else None,
            "total_seconds": total,
            "client_rounds_per_sec": n_rounds * n_clients / max(total, 1e-9),
            "client_chunk_size": config.client_chunk_size,
            "mesh": mesh,
            # Robustness telemetry (quorum policy, docs/ROBUSTNESS.md): always
            # present so downstream consumers (bench.py) need no key checks.
            "rounds_rejected": telemetry["rounds_rejected"],
            # Run telemetry (docs/OBSERVABILITY.md): post-warmup XLA compile
            # count — 0 on a shape-stable run; None when telemetry is off.
            "telemetry_level": config.telemetry_level.lower(),
            "post_warmup_compiles": (
                post_warmup_compiles["count"]
                if post_warmup_compiles is not None else None
            ),
            "mean_survivor_count": (
                float(np.mean(telemetry["survivor_counts"]))
                if telemetry["survivor_counts"] else None
            ),
            # Client statistics (telemetry/client_stats.py): total clients
            # flagged by the per-round anomaly detector over the run — 0 on a
            # clean run; None when client_stats is off.
            "clients_flagged": (
                telemetry["clients_flagged"]
                if client_stats_cfg is not None else None
            ),
            # Async federation (robustness/arrivals.py): simulated-clock
            # speedup of deadline rounds over the wait-for-everyone sync
            # counterfactual, the final simulated clock, and the mean
            # staleness-buffer occupancy — all None when async_mode='off'.
            # The speedup ratio covers the rounds THIS process executed (a
            # per-run measurement, like round_seconds); the clock is read
            # from the carried buffer state, so a resumed run reports the
            # CUMULATIVE simulated time — consistent with the sim_clock_s
            # the records carry.
            "async_speedup_ratio": (
                telemetry["sim_sync_s"] / telemetry["sim_async_s"]
                if async_ctl is not None and telemetry["sim_async_s"] > 0
                else None
            ),
            "sim_clock_seconds": (
                float(jax.device_get(async_state["clock"]))
                if async_ctl is not None else None
            ),
            "mean_buffer_occupancy": (
                float(np.mean(telemetry["buffer_occupancy"]))
                if telemetry["buffer_occupancy"] else None
            ),
            # Streamed residency (parallel/streaming.py): run-total transfer
            # accounting and the fraction of host->HBM upload time the
            # double-buffered prefetch hid behind compute — the number
            # bench.py's `stream` leg records and compare_bench.py gates
            # (--stream-overlap-threshold). All None when resident.
            "client_residency": config.client_residency,
            "stream_overlap_ratio": (
                streamer.overlap_ratio() if streamer is not None else None
            ),
            "stream_h2d_bytes": (
                streamer.totals["h2d_bytes"] if streamer is not None else None
            ),
            "stream_d2h_bytes": (
                streamer.totals["d2h_bytes"] if streamer is not None else None
            ),
            # Cohort-draw replay cost (ops/sampling.py samplers): run-total
            # host seconds spent re-deriving cohorts from the round-key
            # chain — the `sample` phase's run total, the number the
            # participation_sampler knob exists to shrink. None when
            # resident (no host replay happens).
            "participation_sampler": config.participation_sampler,
            "stream_sample_seconds": (
                streamer.totals["sample_seconds"]
                if streamer is not None else None
            ),
            # Distributed shard store (streamed x multihost;
            # parallel/streaming.DistributedCohortStreamer): this host's
            # ownership summary and the run-total assembly traffic — spill
            # rows (the per-round ownership imbalance) and the bytes they
            # moved over DCN. None on single-process runs, the off-gate
            # convention.
            "stream_dcn_bytes": (
                streamer.totals.get("dcn_bytes") if mh else None
            ),
            "multihost_summary": (
                {
                    "hosts": n_procs,
                    "host_id": jax.process_index(),
                    "owned_clients": store.n_owned,
                    "shard_bytes": int(
                        store.data_bytes()
                        + (store.state_bytes()
                           if store.state is not None else 0)
                    ),
                    "spill_rows": int(streamer.totals.get("spill_rows", 0)),
                    "dcn_bytes": int(streamer.totals.get("dcn_bytes", 0)),
                }
                if mh else None
            ),
            # Predictive cost model (telemetry/costmodel.py): the schema-v6
            # costmodel sub-object the run's last record carried — None when
            # cost_model_trace is unset, the trace was empty, or the run was
            # preempted before its last round.
            "costmodel": telemetry["costmodel"],
            # Always-on client valuation (telemetry/valuation.py): the
            # top/bottom client tables + the latest audit (bench.py's
            # ``valuation`` leg reads these); ``valuation_state`` is the
            # live ValuationState for library callers/scripts that need the
            # full vector (like ``algorithm``, an object — not JSON). Both
            # None when client_valuation='off'.
            "client_valuation": config.client_valuation,
            "valuation": (
                vstate.summary(telemetry["valuation_last_audit"])
                if vstate is not None else None
            ),
            "valuation_state": vstate,
            # GTG cross-round memo reuse (config.gtg_cross_round_memo,
            # ROADMAP item 4b): the last walk's cross-round subset-utility
            # hit rate — None when the memo is off or no walk ran.
            "gtg_memo_hit_rate": getattr(
                algorithm, "gtg_memo_hit_rate", None
            ),
            # Open-world population (robustness/population.py): the
            # registration stream's run summary — growth ratio, alive count,
            # total joins/departs, and how many quorum rejections coincided
            # with in-cohort departures (bench.py's churn leg reads these).
            # "static" mode reports None, the off-gate convention.
            "population": config.population,
            "population_summary": (
                pop.summary(telemetry["churn_rejected"])
                if pop is not None else None
            ),
            # Distributed tracing (telemetry/spans.py): this host's span
            # journal path + run-total span counts and worst barrier skews —
            # None when span_trace='off', the off-gate convention.
            "span_trace": config.span_trace,
            "span_summary": span_summary,
            "preempted_at": preempted_at,
        }
    finally:
        tracer.finish()


def run_sweep(config_or_spec, dataset=None, client_data=None):
    """Multi-experiment front door (sweep/): run a fleet of experiments
    — vmapped over an experiment axis where the points allow, scheduled
    through config-hash-grouped warm programs where they don't. Thin
    re-export so ``simulator`` stays the one entry module; the engine
    lives in sweep/engine.py (imported lazily — solo runs never pay the
    import)."""
    from distributed_learning_simulator_tpu.sweep import (
        run_sweep as _run_sweep,
    )

    return _run_sweep(config_or_spec, dataset=dataset,
                      client_data=client_data)


def main(argv: list[str] | None = None):
    from distributed_learning_simulator_tpu.config import get_config
    from distributed_learning_simulator_tpu.sweep.spec import SweepSpec

    config = get_config(argv)
    if SweepSpec.active(config):
        # Sweep knobs set (sweep_seeds / sweep_points): the process runs
        # a FLEET of experiments instead of one (sweep/engine.py).
        return run_sweep(config)
    result = run_simulation(config)
    return result


if __name__ == "__main__":
    main()
