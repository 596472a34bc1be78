"""Experiment configuration + CLI.

Parity with the reference's config surface (config.py:9-18 adds
``--distributed_algorithm --worker_number --round`` on top of the external
``DefaultConfig``'s ``--dataset_name --model_name --epoch --learning_rate
--optimizer_name --log_level`` — observed at simulator.sh:1-2), plus the
knobs this framework adds natively: partitioning (IID / Dirichlet), mesh
size, quantization levels, Shapley hyperparameters, checkpointing.
"""

from __future__ import annotations

import argparse
import dataclasses
from dataclasses import dataclass, field
from typing import Any

# Valid telemetry_level values (semantics: telemetry/ and
# docs/OBSERVABILITY.md). Defined here — not in the telemetry package —
# so validate() stays import-light (telemetry's submodules import jax);
# the package re-exports it.
TELEMETRY_LEVELS = ("off", "basic", "detailed")

# Valid client_stats values (telemetry/client_stats.py). Same
# import-light placement rationale as TELEMETRY_LEVELS.
CLIENT_STATS_LEVELS = ("off", "on")

# Valid participation_sampler values (ops/sampling.py, which re-exports
# this as SAMPLERS). Same import-light placement rationale as
# TELEMETRY_LEVELS — ops.sampling imports jax.
PARTICIPATION_SAMPLERS = ("exact", "hashed")

# Valid sweep_strategy values (sweep/spec.py re-exports this). Same
# import-light placement rationale as TELEMETRY_LEVELS — the sweep
# engine imports jax.
SWEEP_STRATEGIES = ("auto", "vmapped", "scheduled")

# Registry names of the Shapley servers — the one copy config.validate()
# and sweep/spec.py both refuse sweeps against (their post_round drives
# data-dependent subset evaluation no shared program can serve).
SHAPLEY_ALGORITHMS = ("multiround_shapley_value", "GTG_shapley_value")

# Valid population values (robustness/population.py). Same import-light
# placement rationale as TELEMETRY_LEVELS — the population module pulls
# in the sampler implementations.
POPULATION_MODES = ("static", "dynamic")


@dataclass
class ExperimentConfig:
    # --- reference-parity flags (config.py:16-18, simulator.sh:1-2) --------
    dataset_name: str = "mnist"
    model_name: str = "lenet5"
    distributed_algorithm: str = "fed"
    worker_number: int = 4
    round: int = 10
    epoch: int = 2  # local epochs per round
    learning_rate: float = 0.01
    optimizer_name: str = "SGD"
    log_level: str = "INFO"
    dataset_args: dict[str, Any] = field(default_factory=dict)
    # Extra keyword arguments forwarded to the model constructor
    # (models/registry.py get_model), e.g. {"fold_stage1": false} to disable
    # the W-folded stage-1 layout on resnet18/34 — required to resume
    # checkpoints written by pre-fold builds (the fold changes the parameter
    # TREE STRUCTURE, so resume's structure check rejects mixed configs).
    # CLI: --model_args '{"fold_stage1": false}' (JSON object).
    model_args: dict[str, Any] = field(default_factory=dict)

    # --- training ----------------------------------------------------------
    batch_size: int = 32
    momentum: float = 0.0
    weight_decay: float = 0.0
    dampening: float = 0.0
    nesterov: bool = False
    seed: int = 0
    reset_client_optimizer: bool = True
    # Dtype of the per-client DIVERGED params/grads/momenta during a local
    # run (FedAvg family). "bfloat16" halves the round's dominant HBM
    # traffic at large-model scale (per-client state is ~3x param bytes per
    # in-flight client); the f32 global model remains the broadcast source
    # every round, aggregation accumulates in f32, and every bf16 cast and
    # param store uses hash-dither stochastic rounding with a per-client
    # salt (engine._sr_to_bf16 — plain round-to-nearest measurably stalls
    # long-horizon training; docs/PERFORMANCE.md). Requires
    # reset_client_optimizer=True (persistent f32 optimizer state would
    # mix dtypes across rounds). Worth it for large models (ResNet-18:
    # +9% round rate at f32-parity accuracy); off by default.
    local_compute_dtype: str = "float32"
    # In-step data augmentation (ops/augment.py): "none" or "cifar"
    # (random flip + pad-4 random crop). Replaces the reference's external
    # dataset-transform hook (transform_dataset, SURVEY §2.4) with a pure
    # batched op fused into the round program. FedAvg-family only.
    augment: str = "none"
    # FedAvg aggregation rule (ops/aggregate.py): "mean" (dataset-size-
    # weighted, the reference's only rule), or the Byzantine-robust
    # "median" / "trimmed_mean" (drop trim_ratio of extremes per
    # coordinate) / "krum" (pick the client update nearest its neighbors;
    # trim_ratio doubles as the assumed Byzantine fraction). Robust rules
    # materialize the full per-client parameter stack, so large models cap
    # the feasible client count.
    aggregation: str = "mean"
    trim_ratio: float = 0.1
    # --- failure model (robustness/faults.py; docs/ROBUSTNESS.md) ----------
    # Per-round client fault injection drawn inside the jitted round from
    # the round key: "none" | "dropout" (never trains; excluded + state
    # frozen) | "straggler" (trains but upload arrives late; excluded) |
    # "corrupt_nan" (uploads all-NaN params at full weight) |
    # "corrupt_scale" (uploads its update scaled 100x — finite Byzantine
    # garbage). FedAvg-family and sign_SGD (dropout/straggler only; a 1-bit
    # vote has no parameter-space garbage to inject); the Shapley
    # algorithms refuse any failure model (their utility memo assumes a
    # fixed cohort). Composes with participation_fraction: a
    # sampled-but-failed client contributes nothing.
    failure_mode: str = "none"
    failure_prob: float = 0.0
    # Round-correlated outages: with probability `failure_correlation` a
    # client's failure draw is replaced by one draw SHARED across the
    # round's cohort — marginal rate stays failure_prob, failures cluster
    # into bad rounds (1.0 = all-or-nothing rounds).
    failure_correlation: float = 0.0
    # Re-rolls WHICH clients fail without touching cohort sampling,
    # training batches, or payload keys (fold_in-decoupled stream).
    failure_seed: int = 0
    # --- open-world population (robustness/population.py) -------------------
    # "static" (default): the fixed client population every prior build
    # assumed — the exact pre-feature program (bit-identical history,
    # byte-identical records, config_hash unchanged, 0 post-warmup
    # compiles; the established off-gate contract). "dynamic": an
    # open-world population driven by a round-key-chained registration
    # stream — per round, new clients JOIN (``join_rate``; their data
    # shards are drawn over a growing index space), existing clients
    # DEPART (``depart_rate``; departed indices are masked out of the
    # hashed sampler's first-k-distinct stream and never resampled), and
    # a planted cohort DRIFTS (``drift_fraction``/``drift_factor``:
    # graded label-noise ramping in on a schedule). The per-round cohort
    # stays pinned at the STARTUP population's cohort size, so the
    # compiled round program never changes shape while N grows. Requires
    # client_residency='streamed' + participation_sampler='hashed' +
    # participation_fraction < 1 and the FedAvg family (fed, fed_quant);
    # composes with faults/quorum (a round whose survivors fall below
    # min_survivors after mid-round departures is rejected in-program,
    # previous global retained) and single-host mesh; refuses async
    # mode, valuation audits, the threaded oracle, and
    # the vmapped sweep strategy — each with the blocking cause named
    # (docs/ROBUSTNESS.md § Dynamic populations).
    population: str = "static"
    # Decouples the registration stream from every other round-key
    # consumer (the PR 2/6 fold_in discipline): re-rolling it changes
    # WHO joins/departs without touching cohort sampling, training
    # batches, fault draws, or payload keys.
    population_seed: int = 0
    # Expected joins per round: floor(join_rate) clients join every
    # round, plus one more with probability frac(join_rate) (drawn from
    # the registration stream). Integer rates give a deterministic
    # growth schedule.
    join_rate: float = 0.0
    # Per-round departure probability of each alive client. Departures
    # are capped so the alive population never falls below the pinned
    # cohort size (the sampler must still fill a cohort); a departure
    # that hits a client sampled in the SAME round zeroes its
    # contribution in-program (quorum-visible).
    depart_rate: float = 0.0
    # Fraction of the STARTUP population planted as a drifting-quality
    # cohort: member i's labels are progressively corrupted toward its
    # grade (drift_factor * rank/m of its samples re-labeled uniformly
    # at random), ramping linearly over the run — the engineered ground
    # truth the streaming valuation is measured against.
    drift_fraction: float = 0.0
    # Peak label-corruption fraction of the worst drifting client.
    drift_factor: float = 0.5
    # --- asynchronous federation (robustness/arrivals.py) -------------------
    # "off" (default): every algorithm runs its exact synchronous-round
    # program (the async machinery is never constructed — trace-time
    # gated like failure_mode). "on": deadline rounds with buffered
    # staleness-weighted aggregation — clients beating round_deadline
    # contribute fresh, late uploads land in a device-resident FedBuff-
    # style buffer applied (with a polynomial staleness discount) once
    # async_buffer_size uploads accumulate. FedAvg family only (fed,
    # fed_quant); sign_SGD, the Shapley servers, and the threaded oracle
    # refuse. round_deadline=inf reproduces sync FedAvg bit-for-bit from
    # the compiled async program (tests/test_async.py).
    async_mode: str = "off"
    # Simulated per-client upload latency, drawn per round from the round
    # key via a fold_in-decoupled stream (activating it re-rolls nothing
    # else): "bimodal" = persistent 80/20 fast/slow population x uniform
    # [0.5, 1.5) jitter; "lognormal" = population factor x
    # exp(arrival_sigma * N(0,1)). Required (non-"none") when
    # async_mode='on'.
    arrival_model: str = "none"
    # Share of the population that is persistently slow, and how much
    # slower it is (the 80/20 heterogeneity knob: defaults model 20% of
    # clients at 8x the upload latency).
    arrival_slow_fraction: float = 0.2
    arrival_slow_factor: float = 8.0
    # Spread of the lognormal per-round jitter (lognormal model only).
    arrival_sigma: float = 0.5
    # Re-rolls WHICH clients are slow (and their jitter) without touching
    # cohort sampling, training batches, failure draws, or payload keys.
    arrival_seed: int = 0
    # Simulated-time budget a round waits for uploads (same units as the
    # arrival model's latencies; a fast client's mean latency is ~1.0).
    # inf = wait for everyone — the synchronous degenerate case.
    round_deadline: float = float("inf")
    # FedBuff K-of-N trigger: the staleness buffer's accumulated late
    # uploads are applied once their count reaches this.
    async_buffer_size: int = 8
    # Exponent of the polynomial staleness discount (1 + s)^(-alpha)
    # weighting a late upload s rounds after its round closed. 0 = full
    # weight regardless of staleness.
    staleness_alpha: float = 0.5
    # Quorum policy (host loop + round program): a round whose survivor
    # count falls below min_survivors — or whose aggregate is non-finite —
    # is REJECTED in-program: the previous global model is retained, and
    # rounds_rejected / survivor_count land in the metrics record and
    # result dict. 0 disables the survivor floor (the non-finite guard
    # still engages whenever a failure model is active).
    min_survivors: int = 0
    # --- server optimizer (FedOpt family; exceeds the reference) -----------
    # "none" = plain FedAvg (the reference's fixed behavior: the aggregate IS
    # the new global model). "sgd"/"adam" treat (prev_global - aggregate) as
    # a pseudo-gradient and apply a server-side optimizer step: FedAvgM with
    # sgd+momentum, FedAdam with adam (Reddi et al., "Adaptive Federated
    # Optimization"). sgd with lr=1.0 and momentum=0 is exactly FedAvg.
    server_optimizer_name: str = "none"
    server_learning_rate: float = 1.0
    server_momentum: float = 0.0

    # --- data partitioning (data/partition.py) -----------------------------
    partition: str = "iid"  # iid | dirichlet
    dirichlet_alpha: float = 0.1
    # Cap on the packed per-client shard size. Every client scans
    # max-shard-size batches per epoch (fixed shapes), so one giant client
    # under extreme Dirichlet skew multiplies EVERY client's step count;
    # capping truncates outlier shards (their extra samples are dropped).
    # None = no cap.
    max_shard_size: int | None = None
    n_train: int | None = None  # subsample for fast runs/tests
    n_test: int | None = None
    data_dir: str | None = None

    # --- quantization (algorithms/fed_quant.py) ----------------------------
    quant_levels: int = 256
    qat: bool = True
    # Per-round per-client local evaluation (FedAvg family: fed,
    # fed_quant): every client's uploaded model is evaluated on the test
    # set BEFORE aggregation, with the post-aggregation global accuracy
    # logged alongside — parity with reference
    # workers/fed_quant_worker.py:55-69. Requires materializing the
    # per-client parameter stack (the fused memory-bounded aggregation
    # path can't serve it), so None = auto: on for fed_quant at cohorts
    # <= 32 (the reference ran 4-8 workers), off otherwise, preserving the
    # large-cohort memory envelope. Explicit True forces it on (fed too);
    # False disables; True with other algorithms is rejected.
    client_eval: bool | None = None

    # --- learning-rate schedule (FedAvg family) -----------------------------
    # Client optimizers reset every round, so the schedule sets each ROUND's
    # effective lr: "constant" | "cosine" (decay to lr_min_factor x lr over
    # lr_schedule_rounds, default the whole run) | "step" (multiply by
    # lr_step_gamma every lr_step_size rounds). Exceeds the reference (its
    # lr is fixed for the whole run, simulator.sh:1); added because
    # constant-lr runs at flagship scale stall or pass through transient
    # collapses (docs/PERFORMANCE.md).
    lr_schedule: str = "constant"
    lr_schedule_rounds: int | None = None  # horizon; None = config.round
    lr_min_factor: float = 0.0
    lr_step_size: int = 30
    lr_step_gamma: float = 0.1

    # --- Shapley (algorithms/shapley.py) ------------------------------------
    round_trunc_threshold: float | None = None
    gtg_eps: float = 1e-3
    gtg_last_k: int = 10
    gtg_converge_criteria: float = 0.05
    # Cap on GTG permutations per round. None = auto ``max(500, 2N)`` at
    # the actual client count N: one GTG sampling iteration draws N
    # permutations (one starting with each worker,
    # GTG_shapley_value_server.py:42-49) and the convergence test needs
    # more than ``max(30, N)`` marginal records, so any cap below 2N can
    # never run a converged estimate — an explicit cap below N is
    # rejected at round-fn build (GTGShapley.check_cohort).
    gtg_max_permutations: int | None = None
    # Cap on test samples used for SUBSET-utility evaluations (the round's
    # reported test metric always uses the full set). None = full set (the
    # reference's behavior). At large N the GTG round is compute-bound on
    # subset inference (tens of thousands of subset models x the whole test
    # set per round); Monte-Carlo SV noise dwarfs eval-subsampling noise,
    # so a few-thousand-sample cap buys a near-linear round-time cut.
    shapley_eval_samples: int | None = None
    # Subset models evaluated per batched XLA call by the Shapley subset
    # evaluator. Each call re-reads the full [n_clients, params] stack for
    # its weighted means, so at large N a larger chunk amortizes that read
    # across more subsets (N=1000 cnn_tpu: the stack is 1.8 GB); the
    # ceiling is activation memory (chunk models x eval-batch activations
    # resident at once).
    shapley_eval_chunk: int = 16
    # Dtype the subset evaluator reads the client-params stack in.
    # "auto" (default) resolves per algorithm (ADVICE r5): "float32" for
    # multiround_shapley_value — the documented exact-parity path, with no
    # Monte-Carlo noise to hide bf16 rounding in — and "bfloat16" for
    # GTG_shapley_value, where halving the per-call stack read (the
    # dominant HBM traffic of a large-N round) is measured fidelity-free.
    # Either aggregation path still ACCUMULATES in f32 (tensordot
    # preferred_element_type / f32 cumulative sums) and the produced
    # subset model is f32. Utilities feed an argmax accuracy, so the
    # measured GTG SV perturbation vs "float32" is below Monte-Carlo noise
    # (tests/test_shapley.py::test_shapley_eval_dtype_agreement). An
    # explicit "float32"/"bfloat16" wins for both algorithms.
    shapley_eval_dtype: str = "auto"
    # How GTG materializes a permutation's prefix models
    # (algorithms/shapley.py): "cumsum" (default) gathers each
    # permutation's clients once in walk order and takes every prefix
    # aggregate from one streamed weighted cumulative sum — O(P) HBM bytes
    # per evaluated prefix instead of the masked path's O(N*P/chunk) share
    # of a full client-stack re-read — with the cross-permutation memo and
    # eps-truncation semantics intact (a truncated walk just stops
    # streaming; nothing is recomputed). "masked" keeps the per-prefix
    # mask-weighted reduction as the differential-testing oracle; the two
    # modes draw identical permutations from a fixed seed and agree
    # exactly in f32 (tests/test_shapley.py).
    gtg_prefix_mode: str = "cumsum"

    # --- execution ----------------------------------------------------------
    # "vmap": the fast path — one jitted round program over the client axis.
    # "threaded": thread-per-client over the native C++ queue/pool runtime
    # (the reference's architecture, servers/server.py + simulator.py:60-69;
    # FedAvg only). Semantically equivalent, ~orders slower; exists for
    # architecture parity and as a differential-testing oracle.
    execution_mode: str = "vmap"
    mesh_devices: int | None = None  # None = single-device vmap path
    # Multi-host (DCN): initialize jax.distributed before device discovery so
    # jax.devices() spans every host's chips and the same mesh/sharding code
    # runs the client axis over ICI within a slice and DCN across slices.
    # Replaces the reference's dormant multi-process path
    # (servers/server.py:11-13, hard-disabled at simulator.py:56). With only
    # --multihost set, relies on the Cloud TPU pod auto-configuration; the
    # explicit coordinator flags cover CPU/GPU clusters and tests.
    multihost: bool = False
    coordinator_address: str | None = None
    num_processes: int | None = None
    process_id: int | None = None
    # Max clients trained concurrently inside one round program. None = all
    # at once (pure vmap). At large N the per-client params/grads/momentum
    # copies and activations exceed HBM; chunking runs vmap-ed chunks
    # sequentially (lax.map) with identical semantics. 0 = auto: computed
    # at startup from the same per-client footprint model the OOM
    # diagnostics use (~4x f32 param bytes per in-flight client, 60% of
    # per-device HBM x mesh size), clamped to the cohort.
    client_chunk_size: int | None = None
    # Size-aware work scheduling for heterogeneous (Dirichlet) shards on the
    # fused FedAvg path: clients are sorted by sample count and grouped into
    # chunks whose scan length matches the chunk's LARGEST member, instead
    # of every client scanning the padded global maximum. Same per-epoch
    # sample coverage (each real sample still visited exactly once per
    # epoch); batch composition — hence the exact SGD trajectory — differs
    # the way any reshuffle does. Per-client OPTIMIZER STEP COUNTS also
    # change: skipped masked-slot steps were real (zero-grad) steps, so
    # with weight_decay > 0 or reset_client_optimizer=False results differ
    # beyond reshuffle noise — matching the reference's per-worker loops
    # (each worker steps only over its own data); set False for
    # bit-comparability with the unscheduled path under those settings
    # (see algorithms/fedavg.py). Skipped automatically when it cannot help
    # (uniform shards) or cannot apply (mesh/multihost sharding, client
    # sampling, materializing algorithms, unchunked rounds).
    bucket_client_work: bool = True
    # Where per-client arrays (data shards + persistent algorithm state)
    # live between rounds. "resident" (default): the full [n_clients, ...]
    # stacks are device-resident for the whole run — the exact
    # pre-feature program, trace-time gated like failure_mode/async_mode.
    # "streamed": the full-N arrays live in a host-side shard store
    # (data/residency.py) and only the sampled cohort's slice is uploaded
    # per dispatch, with the NEXT dispatch's cohort prefetched while the
    # current one computes (parallel/streaming.py) — device memory sizes
    # by the cohort, not the population, which is what lets
    # million-client populations run on one host
    # (docs/PERFORMANCE.md § Streamed client state). Bit-identical to
    # 'resident' at any N: the cohort index sequence is host-replayed
    # from the round-key chain, so sampling/fault/training draws are
    # unchanged. vmap execution only; single-host mesh sharding
    # COMPOSES (the streamer uploads the cohort slice straight into the
    # client-axis PartitionSpec layout — the cohort must divide
    # mesh_devices), and so does MULTIHOST (the distributed shard
    # store: each process owns an N/num_hosts client slice and serves
    # its own members of every round's owner-permuted cohort straight
    # into its addressable shards of the client-axis PartitionSpec —
    # data/residency.py + parallel/streaming.py; needs a mesh spanning
    # every process and the hashed sampler for sampled cohorts, with
    # the remaining composition refusals cause-named in validate() and
    # docs/ROBUSTNESS.md). Refuses algorithms that don't opt in
    # (Algorithm.supports_streamed_residency — the Shapley family's
    # subset re-evaluation assumes a resident stack).
    client_residency: str = "resident"
    # Fraction of clients sampled (without replacement) to train+aggregate
    # each round (FedAvg-family). 1.0 = all clients, the reference's fixed
    # behavior; <1.0 is standard FL client sampling — and unlike the
    # reference's barrier (fed_server.py:75-77, which hangs forever if a
    # client goes missing), non-participants simply sit the round out.
    participation_fraction: float = 1.0
    # HOW the cohort is drawn from the round key (ops/sampling.py).
    # "exact" (default): the bit-identical pre-feature
    # jax.random.choice(replace=False) — a full O(N log N) permutation
    # per round, ~1 s at N=1e6 on a CPU host, which is what left the
    # streamed-residency stream leg host-bound. "hashed": an O(cohort)
    # counter-based Threefry draw (first-k-distinct of a keyed hash
    # stream, duplicates rejected in a fixed small over-draw buffer —
    # no full-N permutation or memory anywhere, numpy-mirrored on the
    # streamed host-replay path). A NEW sampling mode, deliberately not
    # bit-identical to 'exact' (gated and documented like
    # client_residency), but uniform, duplicate-free, deterministic
    # from the round-key chain, and identical between the in-program
    # draw and the host replay by construction. A program-defining knob:
    # 'hashed' lands in config_hash; 'exact' keeps pre-feature hashes
    # (docs/PERFORMANCE.md § Streamed client state has the guidance).
    participation_sampler: str = "exact"
    # Defer each round's metric fetch + post_round by one round so the
    # device->host transfer latency overlaps the next round's compute
    # (significant when the chip sits behind a high-latency link). Auto-
    # disabled for algorithms whose post_round needs same-round metrics
    # (Shapley) and when per-client state must be checkpointed.
    pipeline_rounds: bool = True
    # --- telemetry (telemetry/; docs/OBSERVABILITY.md) ----------------------
    # "off" (default): zero instrumentation — metrics.jsonl keeps the
    # legacy v1 record layout byte-for-byte and the measured program is
    # untouched. "basic": per-round phase timings (monotonic clocks around
    # the dispatch sites; JAX dispatch is async, so device time pools into
    # the host_sync phase), XLA recompile counts with offending function
    # names (any compile after the warmup round is flagged as a
    # shape-instability WARNING), and the per-round peak-HBM watermark —
    # recorded under a schema-versioned "telemetry" sub-object in
    # metrics.jsonl. "detailed": same fields, but every phase fences on
    # its output (block_until_ready) so the split is true per-phase device
    # time; fencing defeats round pipelining's transfer/compute overlap —
    # a measurement mode, not a production mode.
    telemetry_level: str = "off"
    # --- distributed tracing (telemetry/spans.py) ---------------------------
    # "off" (default): zero instrumentation — the exact pre-feature
    # program (byte-identical records, 0 post-warmup compiles,
    # config_hash unchanged). "on": a per-host structured span recorder
    # wraps every phase boundary plus the multihost seams (DCN spill
    # exchange wait-vs-transfer, prefetch worker occupancy, checkpoint
    # shard write + manifest barrier wait, recompile events) and journals
    # them to spans_<host_id>.jsonl in the artifacts dir; the buffer
    # doubles as a crash flight recorder (docs/OBSERVABILITY.md
    # § Distributed tracing). Works at any telemetry_level.
    span_trace: str = "off"
    # Journal directory override. None (default): the run's artifacts
    # dir — which only the PRIMARY host has (non-primary hosts skip
    # set_run_artifacts), so multihost runs that want every host's
    # journal pass a shared directory here. Pure I/O routing, never part
    # of the compiled program (config_hash exempt).
    span_dir: str | None = None
    # Bounded in-memory span ring: overflow increments the record's
    # `dropped` counter instead of blocking the hot path.
    span_buffer_size: int = 4096
    # How many completed spans the flight recorder force-flushes (plus
    # every still-open span) on SIGTERM / quorum rejection / crash.
    span_flush_last_k: int = 64
    # --- per-client statistics (telemetry/client_stats.py) ------------------
    # "off" (default): zero instrumentation — the round program is the
    # exact pre-feature program (same RNG streams, same HLO) and
    # metrics.jsonl records stay at schema v2 or below. "on": the round
    # program additionally computes a compact per-client f32 stats vector
    # (loss before/after, update L2 norm, grad norm, cosine against the
    # aggregate delta, non-finite element count) via streaming per-chunk
    # reductions — works on the fused and bucketed aggregation paths
    # without materializing the per-client parameter stack — stacked
    # [N, S] on device; a host-side median/MAD detector flags anomalous
    # clients per round (flagged_clients / flag_reason in the schema-v3
    # metrics record). sign_SGD reports its per-step majority-vote
    # agreement fraction instead (one shared params tree — there is no
    # per-client delta); fed_quant adds the downlink quantization MSE.
    client_stats: str = "off"
    # Fetch cadence: the [N, S] matrix is computed on device every round
    # but transferred to host (inside the round's single metric fetch, so
    # async dispatch is preserved) only on rounds where
    # round_idx % client_stats_every == 0.
    client_stats_every: int = 1
    # Coordinates in the strided per-client delta probe used for the
    # aggregate-cosine statistic (exact when the model has <= this many
    # parameters); norms and non-finite counts are always exact.
    client_stats_probe: int = 4096
    # Robust z-score threshold of the median/MAD detector; lower = more
    # sensitive (see docs/OBSERVABILITY.md § detector tuning).
    client_stats_mad_threshold: float = 8.0
    # --- always-on client valuation (telemetry/valuation.py) ----------------
    # "off" (default): zero instrumentation — the round program is the
    # exact pre-feature program and metrics.jsonl records stay at schema
    # v6 or below. "on" (requires client_stats='on'; FedAvg family, vmap
    # execution): the round additionally emits a per-cohort streaming
    # contribution score (cosine-vs-aggregate x update-norm over the
    # client-stats probe, unit-L1 normalized) that the host scales by the
    # server loss-delta and folds into a persistent exponentially-decayed
    # per-client valuation vector — a cheap always-on Shapley proxy
    # (schema-v7 ``valuation`` sub-object; docs/OBSERVABILITY.md
    # § Client valuation).
    client_valuation: str = "off"
    # Exponential decay of the valuation fold: participants' entries move
    # v <- decay * v + (1 - decay) * loss_delta * score each round.
    # Higher = longer memory.
    valuation_decay: float = 0.9
    # Audit cadence: every this-many rounds (0 = never) the simulator
    # re-materializes the current cohort's exact uploads (round-key
    # replay) and runs a truncated GTG walk over them
    # (algorithms/shapley.gtg_walk), recording Spearman/Pearson
    # correlation between the streaming vector and the exact SVs — the
    # measured fidelity bound on the cheap estimator. Audits are pure
    # reads (training is untouched) and cost roughly one extra cohort
    # training pass + the walk; they refuse failure models, async mode,
    # non-mean aggregation, persistent client optimizers and multihost
    # (the replay's exactness contract).
    # Single-host mesh_devices > 1 COMPOSES: the audit walk's subset
    # evaluation shards over the mesh, bit-identical to the serial walk
    # (algorithms/shapley.eval_mesh_devices). Caveat, documented not
    # hidden: under mesh the LIVE round's client training is sharded
    # while the replay runs single-placement, so replayed uploads can
    # differ by last-ulp tiling effects — far below the walk's
    # Monte-Carlo noise; the operative contract there is the measured
    # Spearman floor (pinned under mesh), not byte equality.
    valuation_audit_every: int = 0
    # Permutation budget per audit walk (also the number of permutations
    # drawn per truncated sampling iteration). Small-N audits converge
    # within the auto GTG cap; at large N this bounds the walk.
    valuation_audit_permutations: int = 16
    # GTG cross-round subset-utility memo (ROADMAP item 4b): reuse
    # interior subset utilities from the last walk over the SAME cohort
    # (GTG-Shapley's between-round reuse premise: utilities drift slowly
    # once round truncation fires). Off (default) keeps the exact
    # per-round memo semantics; the walk's gtg_memo_hit_rate records how
    # much was reused when on. Realized device savings require
    # gtg_prefix_mode='masked' (its per-subset calls dedup against the
    # seed); under the default 'cumsum' the prefix walker streams every
    # position to keep its carries, so the hit rate measures utility
    # reuse/stability, not work avoided (algorithms/shapley.SubsetMemo).
    # Also governs whether valuation audits seed from the previous audit
    # of the same cohort.
    gtg_cross_round_memo: bool = False
    # Write a jax.profiler trace of the whole run into this directory.
    profile_dir: str | None = None
    # First round the profile trace covers (earlier rounds run untraced).
    # Tracing from round 0 includes the XLA compile and its host events;
    # bench.py's flagship proxy traces from round 1 so its totals describe
    # a steady-state round only.
    profile_from_round: int = 0
    # --- predictive cost model (telemetry/costmodel.py) ---------------------
    # Path to an EXISTING jax.profiler trace directory of this program
    # (a previous run's profile_dir; bench.py's proxy uses its own traced
    # run in-process). When set, the categorized op ledger
    # (utils/tracing.categorize_ops) is evaluated through the roofline
    # model against the checked-in topology table and the run's LAST
    # metrics record carries the schema-v6 ``costmodel`` sub-object —
    # predicted per-round time per topology, bottleneck attribution, and
    # model_error_ratio against this run's measured steady round time
    # (docs/OBSERVABILITY.md § Cost model). None (default): records stay
    # at schema v5 or below byte-for-byte. Pure host-side analysis — it
    # never touches the compiled program, so all three knobs are
    # excluded from config_hash like profile_dir.
    cost_model_trace: str | None = None
    # Rounds the reference trace covers (bench.py's cnn proxy traces 3
    # rounds, its flagship proxy 1): ledger totals are divided by this
    # to get the per-round basis the prediction uses.
    cost_model_trace_rounds: int = 1
    # Topology-table entry (telemetry/topologies.py) the prediction is
    # anchored on — the hardware this run's measured round time comes
    # from; model_error_ratio is predicted-vs-measured on this entry.
    cost_model_topology: str = "v5e-1"
    # --- multi-experiment sweep (sweep/; docs/PERFORMANCE.md § Sweep) ------
    # Comma-separated seed list: run one experiment per seed as a FLEET
    # sharing this config's dataset/partition (data seed stays this
    # config's `seed`; each point's seed drives model init + the training
    # RNG chain). Where every point agrees on the program-defining knobs
    # (seed/learning_rate may vary), the fleet runs as ONE vmapped jitted
    # program — compile paid once, each point's history bit-identical to
    # a solo run with that seed on the shared data. None (default) = no
    # sweep; `python -m distributed_learning_simulator_tpu` dispatches to
    # sweep.run_sweep when set.
    sweep_seeds: str | None = None
    # JSON list of per-point config overrides, e.g.
    # '[{"learning_rate": 0.05}, {"learning_rate": 0.1}]'. Combined with
    # sweep_seeds, every override runs at every seed (the grid).
    # Heterogeneous overrides (program-defining knobs) route through the
    # compile-cache-aware scheduler: points group by config_hash and run
    # sequentially through one warm program per (seed-normalized)
    # program class, with per-point compile reuse recorded.
    sweep_points: str | None = None
    # "auto" (default): vmapped fleet when every point is
    # fleet-compatible, else the scheduler. "vmapped"/"scheduled" force
    # a strategy ("vmapped" refuses with the blocking feature named).
    sweep_strategy: str = "auto"
    # Sweep-level checkpointing: every completed point persists its
    # result + schema-v8 records here; an interrupted sweep resumes with
    # sweep_resume=True, re-running only the missing points (points are
    # RNG-independent, so the stitched sweep is bit-identical).
    sweep_dir: str | None = None
    sweep_resume: bool = False
    # Persistent XLA compilation cache directory: the round program's
    # ~20-45s first compile is skipped on any later run with the same
    # shapes (including across processes). A relative path resolves
    # against the checkout, never the CWD, and JAX_COMPILATION_CACHE_DIR
    # in the environment overrides this field entirely
    # (utils/compile_cache.py). Disable with None, or from the CLI with
    # --compilation_cache_dir none (normalized in validate()).
    compilation_cache_dir: str | None = ".jax_cache"
    # Store packed client shards as uint8-flattened arrays (4x less HBM,
    # TPU-friendly tiling); batches are decoded on the fly in the step.
    compact_client_data: bool = True
    eval_batch_size: int = 512
    log_root: str = "log"
    checkpoint_dir: str | None = None
    checkpoint_every: int = 0  # rounds; 0 = disabled
    # Retention: keep only the newest N checkpoints (GC after each
    # successful save), so week-long chaos/preemption runs don't fill the
    # disk. None = keep all. Keep >= 2 when integrity matters: resume
    # falls back past a corrupt/truncated latest checkpoint to the newest
    # VALID one (utils/checkpoint.py).
    checkpoint_keep_last: int | None = None
    resume: bool = False

    def cohort_size(self, n_clients: int | None = None) -> int:
        """Participants per round: the single source of the sampling formula
        (used by the round builder, the OOM hint, and krum's feasibility
        check — keep them in lockstep)."""
        n = self.worker_number if n_clients is None else n_clients
        if self.participation_fraction >= 1.0:
            return n
        return max(1, round(self.participation_fraction * n))

    def validate(self) -> "ExperimentConfig":
        if self.worker_number < 1:
            raise ValueError("worker_number must be >= 1")
        if self.round < 1:
            raise ValueError("round must be >= 1")
        if self.partition not in ("iid", "dirichlet"):
            raise ValueError(f"unknown partition {self.partition!r}")
        if not 0.0 < self.participation_fraction <= 1.0:
            raise ValueError("participation_fraction must be in (0, 1]")
        if self.participation_sampler.lower() not in PARTICIPATION_SAMPLERS:
            raise ValueError(
                f"unknown participation_sampler "
                f"{self.participation_sampler!r}; known: "
                + ", ".join(PARTICIPATION_SAMPLERS)
            )
        if self.compilation_cache_dir in ("", "none", "None"):
            self.compilation_cache_dir = None
        if self.sweep_strategy not in SWEEP_STRATEGIES:
            raise ValueError(
                f"unknown sweep_strategy {self.sweep_strategy!r}; known: "
                + ", ".join(SWEEP_STRATEGIES)
            )
        if self.sweep_resume and not self.sweep_dir:
            raise ValueError(
                "sweep_resume=True needs sweep_dir (where the completed "
                "points were persisted)"
            )
        if self.sweep_seeds or self.sweep_points:
            # Sweep-wide refusals (the one authoritative copy; sweep/
            # spec.py re-checks per point because overrides can
            # introduce any of these).
            if self.execution_mode.lower() == "threaded":
                raise ValueError(
                    "execution_mode='threaded' does not support sweeps: "
                    "the thread-per-client oracle owns one OS thread per "
                    "client per experiment and shares no compiled "
                    "program; run threaded points as solo runs"
                )
            if self.distributed_algorithm in SHAPLEY_ALGORITHMS:
                raise ValueError(
                    f"algorithm {self.distributed_algorithm!r} does not "
                    "support sweeps: its post_round drives data-dependent "
                    "subset evaluation that must observe every round "
                    "synchronously; run Shapley configs as solo runs"
                )
            if self.multihost:
                raise ValueError(
                    "sweeps do not compose with multihost: every process "
                    "would re-run the whole point list; shard the sweep "
                    "across hosts by splitting the point list instead"
                )
        if self.cost_model_trace_rounds < 1:
            raise ValueError("cost_model_trace_rounds must be >= 1")
        from distributed_learning_simulator_tpu.telemetry.topologies import (
            get_topology,
        )

        get_topology(self.cost_model_topology)  # fail fast on typos
        if not isinstance(self.model_args, dict):
            raise ValueError(
                "model_args must be a dict of model-constructor kwargs "
                '(CLI: a JSON object, e.g. \'{"fold_stage1": false}\')'
            )
        from distributed_learning_simulator_tpu.ops.augment import get_augment

        get_augment(self.augment)  # fail fast on unknown augmentation names
        if self.aggregation.lower() not in ("mean", "median", "trimmed_mean",
                                            "krum"):
            raise ValueError(
                f"unknown aggregation {self.aggregation!r}; known: mean, "
                "median, trimmed_mean, krum"
            )
        if not 0.0 <= self.trim_ratio < 0.5:
            raise ValueError("trim_ratio must be in [0, 0.5)")
        if self.aggregation.lower() == "trimmed_mean":
            from distributed_learning_simulator_tpu.ops.aggregate import (
                trim_count,
            )

            cohort = self.cohort_size()
            if trim_count(cohort, self.trim_ratio) < 1:
                raise ValueError(
                    f"trimmed_mean with trim_ratio={self.trim_ratio} and a "
                    f"cohort of {cohort} trims k=0 clients — a plain mean "
                    "with zero robustness (one NaN upload poisons the "
                    "round); raise trim_ratio or the cohort size so "
                    "trim_ratio * cohort >= 1"
                )
        if self.aggregation.lower() == "krum":
            from distributed_learning_simulator_tpu.ops.aggregate import (
                trim_count,
            )

            cohort = self.cohort_size()
            f = trim_count(cohort, self.trim_ratio)
            if cohort < 2 * f + 3:
                raise ValueError(
                    f"krum needs n >= 2f + 3 participants (cohort={cohort}, "
                    f"assumed Byzantine f={f}); lower trim_ratio or raise "
                    "worker_number/participation_fraction"
                )
        from distributed_learning_simulator_tpu.robustness.faults import (
            MODES as _FAILURE_MODES,
        )

        if self.failure_mode not in _FAILURE_MODES:
            raise ValueError(
                f"unknown failure_mode {self.failure_mode!r}; known: "
                + ", ".join(_FAILURE_MODES)
            )
        if not 0.0 <= self.failure_prob <= 1.0:
            raise ValueError("failure_prob must be in [0, 1]")
        if not 0.0 <= self.failure_correlation <= 1.0:
            raise ValueError("failure_correlation must be in [0, 1]")
        if self.min_survivors < 0:
            raise ValueError("min_survivors must be >= 0")
        if self.min_survivors > self.cohort_size():
            raise ValueError(
                f"min_survivors={self.min_survivors} exceeds the sampled "
                f"cohort size ({self.cohort_size()}); every round would be "
                "rejected — lower it or raise worker_number/"
                "participation_fraction"
            )
        _failure_active = (
            self.failure_mode != "none" and self.failure_prob > 0.0
        )
        if _failure_active:
            # (The Shapley algorithms refuse failure injection too, but in
            # ONE place — their constructors via _check_shapley_config —
            # so the refusal can't drift across an algorithm-name list
            # kept here.)
            if self.execution_mode.lower() == "threaded":
                raise ValueError(
                    "the threaded execution oracle does not model client "
                    "failures; use execution_mode='vmap' with a failure "
                    "model"
                )
        from distributed_learning_simulator_tpu.robustness.arrivals import (
            ARRIVAL_MODES as _ARRIVAL_MODES,
            AsyncFederation,
        )

        if self.arrival_model not in _ARRIVAL_MODES:
            # Checked even at async_mode='off' so a typo fails fast
            # instead of surfacing only when async is later turned on.
            raise ValueError(
                f"unknown arrival_model {self.arrival_model!r}; known: "
                + ", ".join(_ARRIVAL_MODES)
            )
        # The ONE authoritative async_mode / arrival-model gate (unknown
        # mode, arrival_model='none' under async) — from_config raises
        # the same errors direct library users see.
        AsyncFederation.from_config(self)
        if self.async_mode.lower() == "on":
            if not self.round_deadline > 0.0:
                raise ValueError("round_deadline must be > 0 (inf = sync)")
            if self.async_buffer_size < 1:
                raise ValueError("async_buffer_size must be >= 1")
            if self.staleness_alpha < 0.0:
                raise ValueError("staleness_alpha must be >= 0")
            if not 0.0 <= self.arrival_slow_fraction <= 1.0:
                raise ValueError(
                    "arrival_slow_fraction must be in [0, 1]"
                )
            if self.arrival_slow_factor < 1.0:
                raise ValueError("arrival_slow_factor must be >= 1")
            if self.arrival_model == "lognormal" and self.arrival_sigma <= 0.0:
                # sigma is the lognormal jitter spread only; a bimodal
                # run must not be refused over a knob it never reads.
                raise ValueError("arrival_sigma must be > 0")
        if self.checkpoint_keep_last is not None and (
            self.checkpoint_keep_last < 1
        ):
            raise ValueError(
                "checkpoint_keep_last must be >= 1 or None (= keep all)"
            )
        if self.local_compute_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"unknown local_compute_dtype {self.local_compute_dtype!r}; "
                "known: float32, bfloat16"
            )
        if (
            self.local_compute_dtype == "bfloat16"
            and not self.reset_client_optimizer
        ):
            raise ValueError(
                "local_compute_dtype='bfloat16' requires "
                "reset_client_optimizer=True (persistent per-client "
                "optimizer state is f32 and would mix dtypes across rounds)"
            )
        if (
            self.client_eval is True
            and self.distributed_algorithm not in ("fed", "fed_quant")
        ):
            # Reject rather than silently ignore: the telemetry machinery
            # lives in the FedAvg round/post_round pair; the Shapley
            # servers override post_round entirely and sign_SGD keeps one
            # shared params tree (there is no per-client model to score).
            raise ValueError(
                "client_eval=True is only supported for the FedAvg family "
                f"(fed, fed_quant), not {self.distributed_algorithm!r}"
            )
        if self.client_chunk_size is not None and self.client_chunk_size < 0:
            raise ValueError(
                "client_chunk_size must be positive, 0 (auto), or None"
            )
        if self.execution_mode.lower() not in ("vmap", "threaded"):
            raise ValueError(
                f"unknown execution_mode {self.execution_mode!r}; known: "
                "vmap, threaded"
            )
        if self.client_residency.lower() not in ("resident", "streamed"):
            raise ValueError(
                f"unknown client_residency {self.client_residency!r}; "
                "known: resident, streamed"
            )
        if self.client_residency.lower() == "streamed":
            if self.execution_mode.lower() == "threaded":
                raise ValueError(
                    "client_residency='streamed' requires the vmap "
                    "execution mode (the threaded oracle owns its own "
                    "per-worker data)"
                )
            if self.multihost:
                # Streamed x multihost COMPOSES since the distributed
                # shard store landed (data/residency.DistributedShardStore
                # + parallel/streaming.DistributedCohortStreamer): each
                # process owns an N/num_hosts client slice and serves its
                # own cohort members straight into its addressable shards
                # of the client-axis PartitionSpec — only the per-round
                # ownership-imbalance spill (O(sqrt(cohort)) rows) ever
                # crosses DCN. The refinements below are the remaining
                # cause-named refusals (docs/ROBUSTNESS.md composition
                # matrix).
                if self.mesh_devices is None or self.mesh_devices < 2:
                    raise ValueError(
                        "client_residency='streamed' under multihost "
                        "needs mesh_devices set to the GLOBAL device "
                        "count: the distributed shard store serves each "
                        "host's cohort members into its addressable "
                        "shards of the client-axis PartitionSpec, so "
                        "there must be a mesh spanning every process"
                    )
                if (
                    self.participation_fraction < 1.0
                    and self.participation_sampler.lower() != "hashed"
                ):
                    raise ValueError(
                        "client_residency='streamed' under multihost "
                        "requires participation_sampler='hashed' for "
                        "sampled cohorts: every host replays the full "
                        "cohort independently each round, and only the "
                        "O(cohort) hashed draw keeps that replay free "
                        "at million-client populations (the exact "
                        "sampler pays an O(N log N) permutation PER "
                        "HOST per round)"
                    )
                if (
                    self.distributed_algorithm == "fed_quant"
                    and self.participation_fraction < 1.0
                ):
                    raise ValueError(
                        "client_residency='streamed' under multihost "
                        "does not compose with fed_quant at sampled "
                        "cohorts: its uplink stochastic-quantization "
                        "keys split per cohort ROW, so the "
                        "owner-permuted layout would dither each "
                        "client's upload with a different key than "
                        "the 1-process run (silently breaking the "
                        "per-client bit-identity contract the "
                        "draw_pos operand provides for training "
                        "draws); use participation_fraction=1, plain "
                        "'fed', or client_residency='resident'"
                    )
                if self.async_mode.lower() == "on":
                    raise ValueError(
                        "client_residency='streamed' under multihost "
                        "does not compose with async_mode='on': the "
                        "staleness buffer's late-upload row has been "
                        "validated on single-host meshes only; use "
                        "client_residency='resident' for async "
                        "multihost runs"
                    )
                if self.client_stats.lower() == "on":
                    raise ValueError(
                        "client_residency='streamed' under multihost "
                        "does not compose with client_stats='on': the "
                        "per-client stats matrix is client-axis sharded "
                        "across processes and the host-side detector "
                        "fetch would need a cross-host gather every "
                        "round; use resident multihost for client stats"
                    )
                if self.client_valuation.lower() == "on":
                    raise ValueError(
                        "client_residency='streamed' under multihost "
                        "does not compose with client_valuation='on': "
                        "the streaming valuation vector is a full-N "
                        "host array with ONE owner, which the "
                        "host-sharded store deliberately no longer has"
                    )
                if self.participation_fraction >= 1.0 and (
                    (
                        self.distributed_algorithm == "sign_SGD"
                        and self.momentum != 0.0
                    )
                    or not self.reset_client_optimizer
                ):
                    raise ValueError(
                        "client_residency='streamed' under multihost "
                        "does not compose with persistent per-client "
                        "state at full participation (momentum "
                        "sign_SGD / reset_client_optimizer=False): the "
                        "full-population state stack stays "
                        "device-resident across rounds, which the "
                        "per-host store cannot checkpoint-own; sampled "
                        "cohorts (participation_fraction < 1) carry "
                        "state through the owner exchange, or use "
                        "client_residency='resident'"
                    )
        if self.population.lower() not in POPULATION_MODES:
            raise ValueError(
                f"unknown population {self.population!r}; known: "
                + ", ".join(POPULATION_MODES)
            )
        if self.join_rate < 0.0:
            raise ValueError("join_rate must be >= 0")
        if not 0.0 <= self.depart_rate < 1.0:
            raise ValueError("depart_rate must be in [0, 1)")
        if not 0.0 <= self.drift_fraction <= 1.0:
            raise ValueError("drift_fraction must be in [0, 1]")
        if not 0.0 <= self.drift_factor <= 1.0:
            raise ValueError("drift_factor must be in [0, 1]")
        if self.population.lower() == "dynamic":
            # Every refusal names the blocking feature (the PR 2/6/7
            # discipline): dynamic populations are an open-world
            # scenario layer, and each composition below is either
            # pinned by a test or refused here with its cause.
            if self.execution_mode.lower() == "threaded":
                raise ValueError(
                    "population='dynamic' requires the vmap execution "
                    "mode: the thread-per-client oracle spawns one OS "
                    "thread per client at startup and cannot register "
                    "or retire clients mid-run"
                )
            if self.distributed_algorithm not in ("fed", "fed_quant"):
                cause = (
                    "its utility memo assumes a fixed cohort over a "
                    "fixed population"
                    if self.distributed_algorithm in SHAPLEY_ALGORITHMS
                    else "its round program does not take the dynamic-"
                         "population departure operand (FedAvg family "
                         "only: fed, fed_quant)"
                )
                raise ValueError(
                    f"algorithm {self.distributed_algorithm!r} does not "
                    f"support population='dynamic': {cause}"
                )
            if self.client_residency.lower() != "streamed":
                raise ValueError(
                    "population='dynamic' requires client_residency="
                    "'streamed': the resident path bakes the population "
                    "length into every device array shape, so each join "
                    "round would recompile the round program; the "
                    "streamed cohort pipeline is population-size-free "
                    "(the host shard store grows by appending)"
                )
            if self.participation_sampler.lower() != "hashed":
                raise ValueError(
                    "population='dynamic' requires participation_sampler"
                    "='hashed': the exact sampler's O(N log N) "
                    "permutation draw has no maskable stream; the hashed "
                    "first-k-distinct stream masks departed indices "
                    "exactly (ops/sampling.py)"
                )
            if self.participation_fraction >= 1.0:
                raise ValueError(
                    "population='dynamic' requires participation_fraction"
                    " < 1: the cohort is pinned at the startup "
                    "population's sampled size so the compiled round "
                    "program never changes shape while N grows; a "
                    "full-participation cohort would have to grow with "
                    "the population"
                )
            if self.multihost:
                raise ValueError(
                    "population='dynamic' does not compose with "
                    "multihost: joins grow the store and would "
                    "re-partition the distributed shard store's "
                    "ownership bounds mid-run; run dynamic populations "
                    "on one host's mesh"
                )
            if self.async_mode.lower() == "on":
                raise ValueError(
                    "population='dynamic' does not compose with "
                    "async_mode='on': the persistent per-client arrival "
                    "speed table is built into the round program at "
                    "trace time for the startup population — a joined "
                    "client has no speed row; set async_mode='off'"
                )
            if self.valuation_audit_every > 0:
                raise ValueError(
                    "population='dynamic' does not compose with "
                    "valuation audits: the auditor replays cohorts from "
                    "a startup snapshot of the packed shards, which "
                    "churn (joins and drifting labels) invalidates; set "
                    "valuation_audit_every=0 (the streaming valuation "
                    "itself composes — its vector grows with the "
                    "population)"
                )
        if (
            self.shapley_eval_samples is not None
            and self.shapley_eval_samples < 1
        ):
            raise ValueError("shapley_eval_samples must be >= 1 or None")
        if self.shapley_eval_chunk < 1:
            raise ValueError("shapley_eval_chunk must be >= 1")
        if self.shapley_eval_dtype not in ("auto", "float32", "bfloat16"):
            raise ValueError(
                "shapley_eval_dtype must be 'auto', 'float32' or "
                f"'bfloat16', got {self.shapley_eval_dtype!r}"
            )
        if self.gtg_prefix_mode not in ("cumsum", "masked"):
            raise ValueError(
                "gtg_prefix_mode must be 'cumsum' or 'masked', got "
                f"{self.gtg_prefix_mode!r}"
            )
        if self.telemetry_level.lower() not in TELEMETRY_LEVELS:
            raise ValueError(
                f"unknown telemetry_level {self.telemetry_level!r}; known: "
                + ", ".join(TELEMETRY_LEVELS)
            )
        if self.span_trace.lower() not in ("off", "on"):
            raise ValueError(
                f"unknown span_trace {self.span_trace!r}; known: off, on"
            )
        if self.span_buffer_size < 1:
            raise ValueError("span_buffer_size must be >= 1")
        if self.span_flush_last_k < 1:
            raise ValueError("span_flush_last_k must be >= 1")
        if self.client_stats.lower() not in CLIENT_STATS_LEVELS:
            raise ValueError(
                f"unknown client_stats {self.client_stats!r}; known: "
                + ", ".join(CLIENT_STATS_LEVELS)
            )
        if self.client_stats_every < 1:
            raise ValueError("client_stats_every must be >= 1")
        if self.client_stats_probe < 1:
            raise ValueError("client_stats_probe must be >= 1")
        if self.client_stats_mad_threshold <= 0.0:
            raise ValueError("client_stats_mad_threshold must be > 0")
        if self.client_valuation.lower() not in ("off", "on"):
            raise ValueError(
                f"unknown client_valuation {self.client_valuation!r}; "
                "known: off, on"
            )
        if not 0.0 <= self.valuation_decay < 1.0:
            raise ValueError("valuation_decay must be in [0, 1)")
        if self.valuation_audit_every < 0:
            raise ValueError("valuation_audit_every must be >= 0")
        if self.valuation_audit_permutations < 1:
            raise ValueError("valuation_audit_permutations must be >= 1")
        if self.client_valuation.lower() == "on":
            if self.client_stats.lower() != "on":
                # The streaming scores are DERIVED from the client-stats
                # matrix (telemetry/valuation.py) — valuation without the
                # stats machinery has nothing to score.
                raise ValueError(
                    "client_valuation='on' requires client_stats='on' "
                    "(the streaming scores derive from the per-client "
                    "stats matrix)"
                )
            if self.execution_mode.lower() == "threaded":
                raise ValueError(
                    "client_valuation='on' requires the vmap execution "
                    "mode (the threaded oracle computes no in-round "
                    "score vector)"
                )
            if self.distributed_algorithm == "sign_SGD":
                # sign_SGD keeps one shared params tree — there is no
                # per-client update delta to score.
                raise ValueError(
                    "client_valuation='on' is not supported for sign_SGD "
                    "(no per-client update delta to score)"
                )
        if self.valuation_audit_every > 0:
            # The audit replays the cohort's local training exactly from
            # the round key; every condition below would make the replay
            # (or the subset-utility semantics) diverge from the live
            # round — refuse with the cause, never audit garbage.
            if self.client_valuation.lower() != "on":
                raise ValueError(
                    "valuation_audit_every > 0 requires "
                    "client_valuation='on' (there is no streaming vector "
                    "to audit)"
                )
            if self.distributed_algorithm != "fed":
                # fed_quant is deliberately excluded: the live fused
                # path quantizes uploads with PER-CHUNK payload keys
                # (chunked_accumulate per_chunk / the bucketed group
                # split), which a whole-stack replay cannot reproduce —
                # the audit would score re-quantized uploads the server
                # never saw. The Shapley servers already compute exact
                # SVs; sign_SGD has no per-client delta.
                raise ValueError(
                    "valuation audits support distributed_algorithm="
                    f"'fed' only, not {self.distributed_algorithm!r} "
                    "(fed_quant's per-chunk upload-quantization keys "
                    "cannot be replayed exactly on a whole-stack audit; "
                    "the Shapley servers already compute exact SVs)"
                )
            if self.failure_mode != "none" and self.failure_prob > 0.0:
                raise ValueError(
                    "valuation audits refuse failure injection (the "
                    "cohort replay assumes honest uploads, the same "
                    "contract as Shapley scoring); set failure_mode="
                    "'none' or valuation_audit_every=0"
                )
            if self.async_mode.lower() == "on":
                raise ValueError(
                    "valuation audits refuse async_mode='on' (subset "
                    "utilities assume a synchronous cohort); set "
                    "valuation_audit_every=0"
                )
            if self.aggregation.lower() != "mean":
                raise ValueError(
                    "valuation audits assume the weighted-mean "
                    "aggregator (subset utilities are weighted means); "
                    "set aggregation='mean' or valuation_audit_every=0"
                )
            if not self.reset_client_optimizer:
                raise ValueError(
                    "valuation audits require reset_client_optimizer="
                    "True (the replay cannot reconstruct pre-round "
                    "persistent optimizer state)"
                )
            if self.multihost:
                # Single-host mesh sharding COMPOSES (the audit walk's
                # subset evaluation partitions over the mesh,
                # algorithms/shapley.eval_mesh_devices — bit-identical
                # to the serial walk); multihost does not: the audit's
                # cohort replay and data-dependent walk are driven by
                # ONE host process.
                raise ValueError(
                    "valuation audits do not compose with multihost: the "
                    "audit's cohort replay and GTG walk are driven by a "
                    "single host process; run audits on one host's mesh "
                    "(single-process mesh_devices sharding is supported)"
                )
        if self.profile_from_round < 0:
            raise ValueError(
                f"profile_from_round must be >= 0, got "
                f"{self.profile_from_round}"
            )
        if (
            self.gtg_max_permutations is not None
            and self.gtg_max_permutations < 1
        ):
            raise ValueError(
                "gtg_max_permutations must be >= 1 or None (= auto "
                "max(500, 2N))"
            )
        if self.lr_schedule.lower() not in ("constant", "cosine", "step"):
            raise ValueError(
                f"unknown lr_schedule {self.lr_schedule!r}; known: "
                "constant, cosine, step"
            )
        if self.lr_schedule.lower() != "constant":
            if self.distributed_algorithm == "sign_SGD":
                # sign_SGD's lr lives in the vote-apply (torch-SGD parity
                # semantics); a round schedule there is untested territory —
                # reject rather than silently ignore.
                raise ValueError(
                    "lr_schedule is supported for the FedAvg family only, "
                    "not sign_SGD"
                )
            if not 0.0 <= self.lr_min_factor <= 1.0:
                raise ValueError("lr_min_factor must be in [0, 1]")
            if (
                self.lr_schedule_rounds is not None
                and self.lr_schedule_rounds < 1
            ):
                raise ValueError(
                    "lr_schedule_rounds must be >= 1 or None (= whole run)"
                )
            if self.lr_step_size < 1:
                raise ValueError("lr_step_size must be >= 1")
            if not 0.0 <= self.lr_step_gamma <= 1.0:
                raise ValueError("lr_step_gamma must be in [0, 1]")
        server_opt = self.server_optimizer_name.lower()
        if server_opt not in ("none", "", "sgd", "adam"):
            raise ValueError(
                f"unknown server optimizer {self.server_optimizer_name!r}; "
                "known: none, sgd, adam"
            )
        if self.server_learning_rate <= 0.0:
            raise ValueError("server_learning_rate must be > 0")
        if not 0.0 <= self.server_momentum < 1.0:
            raise ValueError("server_momentum must be in [0, 1)")
        if server_opt == "adam" and self.server_momentum:
            raise ValueError(
                "server_momentum is only used by the sgd server optimizer; "
                "adam ignores it — unset one of the two"
            )
        return self


def _add_args(parser: argparse.ArgumentParser) -> None:
    for f in dataclasses.fields(ExperimentConfig):
        if f.name == "dataset_args":
            continue
        arg = f"--{f.name}"
        if f.name == "model_args":
            import json

            parser.add_argument(
                arg, type=json.loads, default={},
                help="JSON object of model-constructor kwargs, e.g. "
                     '\'{"fold_stage1": false}\'',
            )
            continue
        if f.type in ("bool", bool):
            parser.add_argument(arg, type=lambda s: s.lower() in ("1", "true"),
                                default=f.default)
        elif f.name == "client_eval":  # tri-state: auto/None, true, false
            parser.add_argument(
                arg,
                type=lambda s: (
                    None if s.lower() in ("auto", "none")
                    else s.lower() in ("1", "true")
                ),
                default=None,
            )
        elif f.name in ("n_train", "n_test", "mesh_devices", "num_processes",
                        "process_id", "lr_schedule_rounds",
                        "shapley_eval_samples", "gtg_max_permutations",
                        "checkpoint_keep_last"):
            parser.add_argument(arg, type=int, default=None)
        elif f.name in ("round_trunc_threshold", "checkpoint_dir", "data_dir",
                        "profile_dir", "cost_model_trace",
                        "client_chunk_size", "max_shard_size",
                        "coordinator_address", "sweep_seeds",
                        "sweep_points", "sweep_dir", "span_dir"):
            typ = {
                "round_trunc_threshold": float,
                "client_chunk_size": int,
                "max_shard_size": int,
            }.get(f.name, str)
            parser.add_argument(arg, type=typ, default=None)
        else:
            parser.add_argument(arg, type=type(f.default), default=f.default)


def get_config(args: list[str] | None = None) -> ExperimentConfig:
    """Parse CLI args into an ExperimentConfig (reference config.py:22-25)."""
    parser = argparse.ArgumentParser(
        description="TPU-native distributed learning simulator"
    )
    _add_args(parser)
    ns = parser.parse_args(args)
    return ExperimentConfig(**vars(ns)).validate()
