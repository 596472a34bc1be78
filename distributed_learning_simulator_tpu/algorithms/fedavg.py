"""FedAvg: dataset-size-weighted federated averaging.

Replaces the reference's FedServer/FedWorker pair (servers/fed_server.py,
workers/fed_worker.py). One round = one jitted program:

  broadcast global params (vmap in_axes=None — the RepeatedResult broadcast of
  fed_server.py:19-24) -> vmap'd local training, E epochs each
  (fed_worker.py:25-27) -> dataset-size-weighted average over the client axis
  (fed_server.py:44-66,81) -> hooks.

The queue barrier (fed_server.py:75-77) is implicit: a jitted program's
aggregation consumes all clients' outputs by construction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax

from distributed_learning_simulator_tpu.algorithms.base import Algorithm
from distributed_learning_simulator_tpu.ops.aggregate import (
    aggregate,
    weighted_mean,
)
from distributed_learning_simulator_tpu.ops.cohort import (
    cohort_scatter,
    cohort_take,
)
from distributed_learning_simulator_tpu.ops.sampling import (
    draw_cohort,
    draw_cohort_host,
)
from distributed_learning_simulator_tpu.parallel.engine import (
    chunked_accumulate,
    local_steps_unrolled,
    make_local_train_fn,
)
from distributed_learning_simulator_tpu.robustness.arrivals import (
    AsyncFederation,
)
from distributed_learning_simulator_tpu.robustness.faults import (
    FailureModel,
    all_finite,
)
from distributed_learning_simulator_tpu.telemetry.client_stats import (
    ClientStats,
)
from distributed_learning_simulator_tpu.telemetry.valuation import (
    ClientValuation,
)


def round_key_splits(key, with_faults: bool):
    """The round key's split chain — the ONE copy shared by the round
    program (resident and streamed entries), the host-side cohort replay
    (:meth:`FedAvg.cohort_indices`), and the valuation auditor's
    training replay (telemetry/valuation.py), so none of them can drift.
    The extra fault split is gated so failure-free runs keep the exact
    pre-feature RNG streams (bit-compatible histories). Returns
    ``(part_key, train_key, payload_key, agg_key, fault_key)`` with
    ``fault_key=None`` when no failure model is active."""
    if with_faults:
        part_key, train_key, payload_key, agg_key, fault_key = (
            jax.random.split(key, 5)
        )
    else:
        part_key, train_key, payload_key, agg_key = (
            jax.random.split(key, 4)
        )
        fault_key = None
    return part_key, train_key, payload_key, agg_key, fault_key


#: One jitted program per fault-gating flavor of the round-key split:
#: ``round_key -> key_data(round_key_splits(round_key, wf)[0])``. The
#: hashed host replay runs once per round; composing the split +
#: key_data EAGERLY costs ~10 ms of per-op dispatch overhead — 50x the
#: O(cohort) draw itself — so the chain is compiled once and dispatched
#: as one call. Built FROM :func:`round_key_splits` (never a re-spelled
#: split width) so a future change to the split chain flows into the
#: hashed replay automatically — the one-copy discipline.
_HASHED_PART_WORDS_JIT: dict = {}


def _hashed_part_key_words(round_key, with_faults: bool):
    fn = _HASHED_PART_WORDS_JIT.get(with_faults)
    if fn is None:
        def _words(key, _wf=with_faults):
            return jax.random.key_data(round_key_splits(key, _wf)[0])

        fn = jax.jit(_words)
        _HASHED_PART_WORDS_JIT[with_faults] = fn
    return np.asarray(fn(round_key)).ravel()


class FedAvg(Algorithm):
    name = "fed"
    supports_lr_schedule = True  # round_fn accepts the lr_scale operand
    # Asynchronous federation (config.async_mode; robustness/arrivals.py):
    # the round program implements deadline rounds + the staleness buffer
    # (carried via the async_state operand / aux key). fed_quant inherits
    # — its payload transform applies to fresh and late uploads alike.
    supports_async = True
    # Streamed residency (config.client_residency='streamed'): the round
    # builder emits the streamed calling convention natively — the cohort
    # slice arrives as already-gathered operands, the in-program gather/
    # scatter drops out, and the shared cohort_round body keeps the two
    # programs bit-identical. fed_quant inherits.
    supports_streamed_residency = True
    # post_round (client_eval) reads the round's raw client stack and the
    # test batches, never the global the round started from.
    supports_global_donation = True

    def __init__(self, config):
        super().__init__(config)
        # Per-round per-client evaluation (config.client_eval): every
        # client's uploaded model evaluated on the test set BEFORE
        # aggregation, plus the post-aggregation global accuracy — the
        # reference logs this for fed_quant (fed_quant_worker.py:55-69);
        # here it is FedAvg-family machinery any subclass can enable.
        # None = auto: on only for fed_quant at reference-like cohort
        # sizes (<= 32); explicit True forces it (and the materializing
        # path), False disables.
        ce = getattr(config, "client_eval", None)
        if ce is None:
            ce = self.name == "fed_quant" and config.cohort_size() <= 32
            if self.name == "fed_quant" and not ce:
                from distributed_learning_simulator_tpu.utils.logging import (
                    get_logger,
                )

                get_logger().info(
                    "client_eval auto-disabled: cohort size %d > 32 (the "
                    "per-client eval needs the materializing path); pass "
                    "client_eval=True to force it",
                    config.cohort_size(),
                )
        # client_eval materializes the RAW per-client stack through this
        # private flag — NOT by setting keep_client_params, which is the
        # documented subclass contract for receiving the payload-processed
        # stack in aux['client_params'] (base.Algorithm.keep_client_params).
        self._client_eval_enabled = bool(ce)
        self._eval_fn = None
        self._client_eval_jit = None

    def prepare(self, apply_fn, eval_fn):
        self._eval_fn = eval_fn

    @property
    def materializes_client_stack(self) -> bool:
        # Single source for "does the round hold the full cohort stack":
        # make_round_fn allocates by it, the simulator feasibility-checks it.
        return (
            self.keep_client_params
            or self._client_eval_enabled
            or self.config.aggregation.lower() != "mean"
        )

    # jax-level template hooks, parity with fed_server.py:38-42 -------------
    def process_client_payload(self, client_params, key):
        """Per-client payload transform before aggregation (identity here;
        FedQuant overrides with quantize->dequantize)."""
        return client_params, {}

    def post_round(self, ctx):
        if not self._client_eval_enabled:
            return {}
        client_params = ctx.aux.get("client_params_raw")
        if client_params is None:
            # No silent fallback to the payload-transformed stack: that
            # would quietly revert the telemetry to evaluating the
            # quantized upload (the deviation this field exists to avoid).
            raise RuntimeError(
                "client_eval is enabled but the round produced no raw "
                "per-client parameter stack (wiring bug in the round "
                "program)"
            )
        import numpy as np

        if self._client_eval_jit is None:
            # One inference program evaluates every client's model: vmap
            # over the stacked params, the padded test batches broadcast.
            # Inference runs through client_param_transform (fed_quant's QAT
            # fake-quant) — the reference evaluates the QAT-INSTRUMENTED
            # model, i.e. fake-quant stays active in its eval forward pass
            # (fed_quant_worker.py:55-58); for plain fed the transform is
            # None and this is the raw eval.
            transform = self.client_param_transform()
            eval_fn = self._eval_fn

            def eval_one(params, *batches):
                if transform is not None:
                    params = transform(params)
                return eval_fn(params, *batches)

            in_axes = (0,) + (None,) * len(ctx.eval_batches)
            self._client_eval_jit = jax.jit(
                jax.vmap(eval_one, in_axes=in_axes)
            )
        m = self._client_eval_jit(client_params, *ctx.eval_batches)
        accs = np.asarray(m["accuracy"], dtype=np.float64)
        from distributed_learning_simulator_tpu.utils.logging import get_logger

        get_logger().info(
            "round %d: pre-agg client acc mean=%.4f min=%.4f max=%.4f; "
            "post-agg global acc=%.4f",
            ctx.round_idx, accs.mean(), accs.min(), accs.max(),
            ctx.metrics["accuracy"],
        )
        return {
            "client_eval": {
                "pre_agg_accuracy_mean": float(accs.mean()),
                "pre_agg_accuracy_min": float(accs.min()),
                "pre_agg_accuracy_max": float(accs.max()),
                "post_agg_accuracy": float(ctx.metrics["accuracy"]),
            }
        }

    def process_aggregated(self, global_params, key):
        """Aggregated-params transform (identity; FedQuant quantizes the
        broadcast). Returns (params, extra_aux)."""
        return global_params, {}

    def cohort_indices(self, round_key, n_clients: int, alive=None,
                       n_participants=None):
        """Host-replay of the round program's cohort draw (base contract).

        MUST mirror ``split_round_key`` + the in-program
        ``ops/sampling.draw_cohort`` in ``make_round_fn`` exactly:
        part_key is split index 0 of the 4-way (or, with a failure
        model, 5-way) round-key split, and both call sites consume the
        ONE sampler implementation, so they can never drift. Under the
        ``exact`` sampler the streamer runs this on the CPU backend and
        jax PRNG draws are backend-deterministic (the streamed cohort
        is the resident cohort bit-for-bit); under ``hashed`` the
        replay is the O(cohort) numpy mirror of the same keyed-hash
        stream — identical indices by construction, no full-N work.

        ``alive``/``n_participants`` serve ``population='dynamic'``
        (robustness/population.py): the draw runs over the CURRENT
        registered index space (``n_clients`` grows) with departed
        indices masked out of the hashed stream, and the cohort size is
        PINNED at the startup population's (so the round program's
        shapes never change) instead of re-derived from the growing N.
        """
        cfg = self.config
        if n_participants is None:
            n_participants = cfg.cohort_size(n_clients)
        if n_participants == n_clients:
            return None
        with_faults = FailureModel.from_config(cfg) is not None
        sampler = getattr(cfg, "participation_sampler", "exact").lower()
        if sampler == "hashed":
            # O(cohort) replay end to end: the round_key_splits +
            # key_data chain runs as ONE jitted call
            # (_hashed_part_key_words — eager per-op dispatch costs
            # more than the whole hashed draw); the draw itself stays
            # in draw_cohort_host, the one host entry. Bit-identical
            # indices to the in-program draw_cohort by construction.
            return draw_cohort_host(
                None, n_clients, n_participants, sampler,
                key_words=_hashed_part_key_words(round_key, with_faults),
                alive=alive,
            )
        part_key = round_key_splits(round_key, with_faults)[0]
        return draw_cohort_host(part_key, n_clients, n_participants,
                                sampler, alive=alive)

    def local_steps_unrolled(self, shard_size: int) -> int:
        return local_steps_unrolled(
            self.config.epoch, shard_size // self.config.batch_size
        )

    def make_round_fn(self, apply_fn, optimizer, n_clients: int,
                      preprocess=None, client_sizes=None):
        from distributed_learning_simulator_tpu.ops.augment import get_augment

        # Count-dependent feasibility (exact Shapley's 2^N bound, GTG's
        # permutation cap) fires here against the TRUE client count —
        # before any training — rather than in the constructor, which only
        # sees config.worker_number (a caller-supplied ClientData may
        # legitimately differ; ADVICE r4).
        self.check_cohort(n_clients)
        cfg = self.config
        # Streamed residency (config.client_residency): the builder emits
        # the streamed calling convention — cohort slices as operands,
        # no in-program gather/scatter — sharing cohort_round with the
        # resident entry so the two programs cannot drift.
        streamed = (
            getattr(cfg, "client_residency", "resident").lower()
            == "streamed"
        )
        compute_dtype = None
        if getattr(cfg, "local_compute_dtype", "float32") == "bfloat16":
            compute_dtype = jnp.bfloat16
        # Per-client statistics (telemetry/client_stats.py): every cs-gated
        # branch below is a TRACE-TIME conditional — client_stats='off'
        # (the default) compiles the exact pre-feature program, and 'on'
        # consumes no extra RNG, so the two modes train bit-identically.
        cs = ClientStats.from_config(cfg)
        # Always-on client valuation (telemetry/valuation.py): like cs, a
        # TRACE-TIME gate — client_valuation='off' (the default) compiles
        # the exact pre-feature program (no extra output, no extra RNG);
        # 'on' (validated to require client_stats='on') adds one tiny
        # per-cohort score vector derived from the stats matrix the round
        # already computes.
        cv = ClientValuation.from_config(cfg)
        train_args = dict(
            local_epochs=cfg.epoch,
            batch_size=cfg.batch_size,
            param_transform=self.client_param_transform(),
            reset_optimizer=cfg.reset_client_optimizer,
            preprocess=preprocess,
            augment=get_augment(cfg.augment),
            compute_dtype=compute_dtype,
            collect_stats=cs is not None,
        )
        local_train = make_local_train_fn(apply_fn, optimizer, **train_args)
        vtrain = jax.vmap(local_train, in_axes=(None, 0, 0, 0, 0, 0, None))
        # keep_client_params (class OR instance level) = the documented
        # contract: post_round receives the payload-processed stack as
        # aux['client_params']. client_eval's raw-stack request rides the
        # private _client_eval_enabled channel instead.
        keep_processed = self.keep_client_params
        aggregation = cfg.aggregation.lower()
        # Robust rules need every client's params at once (a median has no
        # chunkwise partial sum), so they share the materializing path.
        # The property is the single source — the simulator's feasibility
        # budget checks the same predicate the round program allocates by.
        materialize = self.materializes_client_stack
        chunk = cfg.client_chunk_size
        # Devices the client axis is split over: chunks take their share
        # of clients from every device (parallel/engine.chunked_accumulate).
        shards = cfg.mesh_devices or 1
        frac = cfg.participation_fraction
        n_participants = cfg.cohort_size(n_clients)
        # Failure model + quorum policy (robustness/faults.py): every
        # fm-gated branch below is a TRACE-TIME conditional, so failure-free
        # runs compile the exact pre-feature program (same RNG stream, same
        # HLO). min_survivors without a failure model still activates the
        # quorum guard (survivors are then just the sampled cohort).
        fm = FailureModel.from_config(cfg)
        min_survivors = getattr(cfg, "min_survivors", 0)
        quorum = fm is not None or min_survivors > 0
        # Asynchronous federation (robustness/arrivals.py): like fm/cs,
        # every af-gated branch below is a TRACE-TIME conditional —
        # async_mode='off' (the default) compiles the exact pre-feature
        # program, and the arrival stream is fold_in-decoupled from the
        # round key's splits, so async draws re-roll nothing else. The
        # persistent population speeds are a build-time constant table.
        af = AsyncFederation.from_config(cfg)
        arrival_speeds = (
            af.speed_table(n_clients) if af is not None else None
        )

        # One client at a time (--client_chunk_size 1 on one device): the
        # cohort is a scan over single clients, each trained by
        # local_train's own body with no batch axis (a model's lax.cond
        # stays a branch, no copy is stacked), its steps adding their
        # updates into the round's f32 aggregate as they are computed
        # (parallel/engine.make_local_train_fn, accumulate_updates). How a
        # model of hundreds of millions of parameters trains. What needs a
        # client's parameters whole (payload transforms, corrupted or late
        # uploads, per-client stats) keeps the stacked path.
        add_client_updates = None
        if (
            chunk == 1 and shards == 1 and not materialize
            and fm is None and af is None and cs is None
            and type(self).process_client_payload
            is FedAvg.process_client_payload
        ):
            add_client_updates = make_local_train_fn(
                apply_fn, optimizer, accumulate_updates=True, **train_args
            )

        # --- size-aware work scheduling (config.bucket_client_work) --------
        # The packed-shard discipline makes every client scan
        # shard_size/batch steps — the GLOBAL maximum — even when its real
        # shard is tiny (Dirichlet skew: the BASELINE configs[4] flagship
        # has a 5x spread). Host-side, the per-client sample counts are
        # static data, so the schedule can be static too: sort clients by
        # needed step count, form chunks in that order, and group chunks by
        # the steps their largest member needs; each group slices the slot
        # axis to its own length and runs its own (statically-shaped)
        # chunked scan. Real-sample coverage per epoch is unchanged — a
        # client's samples occupy its first slots, always inside the
        # group's slice — and empty clients are skipped outright (their
        # aggregation weight is 0 and their metrics are 0 either way).
        #
        # Optimizer-step-count caveat (ADVICE r4): a small client's skipped
        # masked-slot steps are real optimizer steps in the unscheduled
        # path — zero-grad steps still apply weight decay, and with
        # reset_client_optimizer=False they decay momentum. So with
        # weight_decay > 0 or persistent client optimizers, scheduling ON
        # vs OFF differs beyond batch-composition reshuffle noise: each
        # client now takes exactly the steps its own data needs. That is
        # the REFERENCE's semantics — each of its workers trains on its
        # own dataset (workers/worker.py:22 delegates to a per-worker
        # Trainer over that worker's loader), so a small client takes
        # fewer steps per epoch there too; the padded-slot steps are
        # this simulator's packing artifact, not behavior to preserve.
        # Runs that need bit-comparability with the unscheduled path under
        # those settings should set bucket_client_work=False.
        bucket_sizes = None
        if (
            client_sizes is not None
            and getattr(cfg, "bucket_client_work", True)
            and not materialize
            and frac >= 1.0
            and chunk is not None
            and chunk > 0
        ):
            bucket_sizes = np.asarray(client_sizes, dtype=np.int64)

        def _bucket_plan(total_steps: int):
            """Static schedule: {steps -> client indices} with every nonzero
            group a union of whole sorted-order chunks (at most the final
            chunk is partial). Empty clients go straight to the s=0 group —
            never into a training chunk. Built at trace time (shapes are
            static under jit)."""
            steps_c = np.minimum(
                -(-bucket_sizes // cfg.batch_size), total_steps
            )
            groups: dict[int, list[np.ndarray]] = {}
            empty = np.flatnonzero(steps_c == 0)
            if empty.size:
                groups[0] = [empty]
            nonzero = np.flatnonzero(steps_c > 0)
            order = nonzero[np.argsort(-steps_c[nonzero], kind="stable")]
            for start in range(0, order.size, chunk):
                sl = order[start : start + chunk]
                groups.setdefault(int(steps_c[sl[0]]), []).append(sl)
            return {s: np.concatenate(g) for s, g in groups.items()}

        def train_clients(global_params, state, x, y, m, keys, lr_scale):
            """Materializing path: returns every client's params stacked
            (needed by Shapley, which re-averages arbitrary subsets)."""
            if chunk is None or chunk >= keys.shape[0]:
                return vtrain(global_params, state, x, y, m, keys, lr_scale)

            # Sequential-over-chunks, vmap-within-chunk (lax.map's batch_size
            # does exactly this): bounds HBM use (per-client param/grad/
            # momentum copies + activations) at chunk size while keeping the
            # whole round one XLA program.
            def one_client(args):
                s, xi, yi, mi, k = args
                return local_train(global_params, s, xi, yi, mi, k, lr_scale)

            return jax.lax.map(
                one_client, (state, x, y, m, keys), batch_size=chunk
            )

        def make_compute(global_params, lr_scale):
            """Per-chunk train+reduce body shared by the plain and bucketed
            fused paths (chunked_accumulate's compute contract). With a
            failure model the chunk trees carry a per-client failed flag:
            corrupt modes damage the RAW upload before the payload
            transform (the same point the materializing path corrupts at),
            dropout freezes the chunk's persistent state."""

            def compute(chunk_trees, pk):
                # Tree layout: (state, x, y, m, keys, w[, late_w][, failed])
                # — the optional members appear in that order exactly when
                # their trace-time feature (af / fm) is active.
                state_c, x_c, y_c, m_c, keys_c, w_c = chunk_trees[:6]
                rest = list(chunk_trees[6:])
                lw_c = rest.pop(0) if af is not None else None
                f_c = rest.pop(0) if fm is not None else None
                cp, ns, tm = vtrain(global_params, state_c, x_c, y_c, m_c,
                                    keys_c, lr_scale)
                if f_c is not None and fm.corrupts_upload:
                    cp = fm.corrupt_stack(cp, f_c)
                if f_c is not None and fm.freezes_client_state:
                    ns = fm.freeze_failed_state(f_c, state_c, ns)
                if cs is not None:
                    # Streaming per-chunk upload stats (O(1) scalars + the
                    # delta probe per client — never the stack), AFTER
                    # corruption: they describe what the server received.
                    tm = cs.add_upload_stats(tm, global_params, cp)
                return reduce_chunk(cp, w_c, pk, lw_c), (ns, tm)

            return compute

        def reduce_chunk(cp, w, pk, lw=None):
            cp, _ = self.process_client_payload(cp, pk)

            # Weighted partial sum accumulated in f32 even when client
            # params are bf16 (local_compute_dtype): a sum over up to
            # 1000 small weighted terms must not round at 8 bits of
            # mantissa. The MXU takes bf16 inputs with an f32
            # accumulator natively.
            def wsum(weights):
                return jax.tree_util.tree_map(
                    lambda p: jnp.tensordot(
                        weights.astype(jnp.float32), p, axes=(0, 0),
                        preferred_element_type=jnp.float32,
                    ),
                    cp,
                )

            if lw is None:
                return wsum(w)
            # Async federation: the late row is a SECOND weighted sum over
            # the same payload-processed chunk (raw discounted weights —
            # normalized at buffer-apply time), kept as a separate
            # tensordot so the fresh row's ops stay identical to the
            # synchronous program (the round_deadline=inf bit-identity
            # contract).
            return (wsum(w), wsum(lw))

        def zero_acc(global_params):
            """Zero accumulator matching reduce_chunk's output: one tree
            for the synchronous reduction, a (fresh, late) pair under
            async federation."""
            z = jax.tree_util.tree_map(jnp.zeros_like, global_params)
            if af is None:
                return z
            return (z, jax.tree_util.tree_map(jnp.zeros_like, global_params))

        def train_and_reduce(global_params, state, x, y, m, keys, norm_w,
                             late_w, failed, payload_key, lr_scale):
            """Fused path: per-chunk weighted partial sums accumulate into
            the aggregate directly, so the full [n_clients, n_params] stack
            never materializes — at 1000 clients x ResNet-18 that stack
            would be ~44 GB, far beyond HBM. ``failed`` is the failure
            model's per-client mask, ``late_w`` the async late-upload
            weights (None when the feature is inactive). Returns
            (aggregate[, late_sum], new_state, train_metrics)."""
            k = keys.shape[0]

            if add_client_updates is not None:
                def one_client(update_sum, client):
                    s, xi, yi, mi, key_i, w = client
                    update_sum, ns, tm = add_client_updates(
                        update_sum, w, global_params, s, xi, yi, mi, key_i,
                        lr_scale,
                    )
                    return update_sum, (ns, tm)

                update_sum, (ns, tm) = jax.lax.scan(
                    one_client, zero_acc(global_params),
                    (state, x, y, m, keys, norm_w),
                )
                # The weights sum to 1: the weighted mean of the clients'
                # parameters is the global model plus this.
                return jax.tree_util.tree_map(
                    jnp.add, global_params, update_sum
                ), ns, tm
            if chunk is None or chunk >= k:
                cp, ns, tm = train_clients(
                    global_params, state, x, y, m, keys, lr_scale
                )
                if failed is not None and fm.corrupts_upload:
                    cp = fm.corrupt_stack(cp, failed)
                if failed is not None and fm.freezes_client_state:
                    ns = fm.freeze_failed_state(failed, state, ns)
                if cs is not None:
                    tm = cs.add_upload_stats(tm, global_params, cp)
                return reduce_chunk(cp, norm_w, payload_key, late_w), ns, tm

            # chunked_accumulate handles the reshape/scan/remainder
            # discipline (remainder participants get their own vmap call so
            # the memory-safe path never silently degrades to materializing
            # the full per-client param stack) and splits payload_key into
            # per-chunk keys itself.
            trees = (state, x, y, m, keys, norm_w)
            if af is not None:
                trees = trees + (late_w,)
            if fm is not None:
                trees = trees + (failed,)
            agg, (ns, tm) = chunked_accumulate(
                trees, chunk,
                make_compute(global_params, lr_scale),
                zero_acc(global_params),
                per_chunk=payload_key, shards=shards,
            )
            return agg, ns, tm

        def train_and_reduce_bucketed(plan, global_params, state, x, y, m,
                                      keys, norm_w, late_w, failed,
                                      payload_key, lr_scale):
            """Fused path with the size-aware schedule: one chunked scan per
            step-count group, each slicing the slot axis to the group's own
            length. Groups accumulate into the same f32 aggregate; per-client
            metrics (and persistent state, if any) scatter back to original
            client positions."""
            n = keys.shape[0]
            agg = zero_acc(global_params)
            # Per-client metrics scatter back to original client positions;
            # the dict is keyed by whatever the compute body reports (loss/
            # accuracy always; the client_stats probe and scalars when on),
            # with skipped empty clients keeping all-zero rows — identical
            # to "training" them on fully masked slots.
            metrics_full = None
            new_state = state
            group_keys = jax.random.split(payload_key, len(plan))
            bsz = cfg.batch_size
            compute = make_compute(global_params, lr_scale)

            # Descending step count: deterministic group order, big work
            # first.
            for gk, (s, idx_np) in zip(
                group_keys, sorted(plan.items(), reverse=True)
            ):
                if s == 0:
                    # Empty clients: zero aggregation weight and zero
                    # metrics — identical to "training" them on fully
                    # masked slots, without the wasted scan.
                    continue
                idx = jnp.asarray(idx_np)
                trees_g = (
                    cohort_take(state, idx),
                    cohort_take(x, idx)[:, : s * bsz],
                    cohort_take(y, idx)[:, : s * bsz],
                    cohort_take(m, idx)[:, : s * bsz],
                    keys[idx],
                    cohort_take(norm_w, idx),
                )
                if af is not None:
                    trees_g = trees_g + (cohort_take(late_w, idx),)
                if fm is not None:
                    trees_g = trees_g + (cohort_take(failed, idx),)
                if idx_np.size <= chunk:
                    partial, (ns_g, tm_g) = compute(trees_g, gk)
                else:
                    partial, (ns_g, tm_g) = chunked_accumulate(
                        trees_g, chunk, compute,
                        zero_acc(global_params),
                        per_chunk=gk, shards=shards,
                    )
                agg = jax.tree_util.tree_map(jnp.add, agg, partial)
                if metrics_full is None:
                    metrics_full = jax.tree_util.tree_map(
                        lambda a: jnp.zeros((n,) + a.shape[1:], a.dtype),
                        tm_g,
                    )
                metrics_full = cohort_scatter(metrics_full, idx, tm_g)
                if state is not None:
                    new_state = cohort_scatter(new_state, idx, ns_g)
            # At least one nonzero group always ran: an all-empty cohort
            # collapses the plan to the single s=0 group, which round_fn
            # routes to the plain path (len(plan) <= 1 -> plan = None).
            assert metrics_full is not None
            return agg, new_state, metrics_full

        def split_round_key(key):
            """Module-level ``round_key_splits`` with this build's fault
            gating baked in (the one split-chain definition — see its
            docstring)."""
            return round_key_splits(key, fm is not None)

        def cohort_round(global_params, state_k, x_k, y_k, m_k, part_sizes,
                         idx, key, keys, lr_scale, async_state,
                         departed=None, draw_pos=None):
            """The round body AFTER the cohort gather — shared verbatim by
            the resident entry (which gathered in-program) and the
            streamed entry (whose operands arrived pre-gathered from the
            host store), which is what makes the two residency modes
            bit-identical by construction. ``idx`` is the cohort's true
            client ids (None = whole population); the returned
            ``new_state_k`` is cohort-sliced and NOT yet scattered.
            ``departed`` (bool[cohort]; population='dynamic' only) marks
            members that depart THIS round — zero contribution, counted
            against the quorum floor. ``draw_pos`` (int[cohort];
            multihost streamed residency only) says which DRAW position
            the client at each cohort row came from: the distributed
            shard store's owner-sharded assembly permutes the cohort
            into owner-contiguous row groups (data/residency
            .plan_owner_assembly), and permuting the per-POSITION draws
            below (training keys, fault flags) by the same map keeps
            every client's training bit-identical to the draw-order
            program — only the aggregation's summation order moves,
            which is the documented resident-vs-mesh tolerance."""
            _, train_key, payload_key, agg_key, fault_key = keys
            if fm is not None:
                failed = fm.draw_failed(fault_key, n_participants)
                if draw_pos is not None:
                    # The fault stream is positional in DRAW order; the
                    # client at row p sat at draw position draw_pos[p].
                    failed = jnp.take(failed, draw_pos, axis=0)
                survival = ~failed
            else:
                failed = None
            if departed is not None:
                # Dynamic population (robustness/population.py): a
                # member that departs mid-round contributes nothing —
                # its weight zeroes and the remaining cohort
                # renormalizes, exactly the dropout-fault discipline;
                # the quorum policy counts it against min_survivors
                # below.
                part_sizes = part_sizes * (~departed).astype(
                    part_sizes.dtype
                )
            client_keys = jax.random.split(train_key, n_participants)
            if draw_pos is not None:
                # Same permutation for the per-position training keys: the
                # client at row p trains with the key of its draw
                # position, exactly as in the draw-order program.
                client_keys = client_keys[draw_pos]
            routed_late = None
            if failed is not None and fm.excludes_update:
                if af is not None and fm.routes_to_buffer:
                    # Straggler fault + arrival model: the upload "arrives
                    # after the deadline" — routed into the staleness
                    # buffer (weight kept; forced late below) instead of
                    # silently discarded, and the client counts as a
                    # survivor (nothing was lost, only delayed). Sync-mode
                    # straggler semantics are untouched.
                    routed_late = failed
                    survival = jnp.ones_like(failed)
                else:
                    # Dropout/straggler: zero aggregation weight. The
                    # weighted mean renormalizes over the SURVIVING
                    # part_sizes (total below shrinks too), and the robust
                    # rules' weights>0 participation mask excludes failed
                    # clients from the per-coordinate statistic.
                    part_sizes = part_sizes * survival.astype(part_sizes.dtype)
            late_w = None
            if af is not None:
                # Arrival model (robustness/arrivals.py): latencies from
                # the fold_in-decoupled stream keyed by TRUE client index
                # — the splits above are untouched, so the deadline=inf
                # degenerate case replays the synchronous run bit-exactly.
                ids = idx if idx is not None else jnp.arange(n_participants)
                latency = af.draw_latency(
                    key, ids, jnp.take(arrival_speeds, ids, axis=0)
                )
                on_time, staleness, discount, eff_latency = af.classify(
                    latency, routed_late
                )
                # Effective latencies: fault-routed stragglers are
                # delayed one deadline, so the simulated clock and the
                # staleness telemetry describe the same arrivals.
                sim_duration, sim_duration_sync = af.durations(eff_latency)
                late_mask = (~on_time) & (part_sizes > 0)
                late_w = (
                    part_sizes.astype(jnp.float32)
                    * discount
                    * late_mask.astype(jnp.float32)
                )
                b_tot = jnp.sum(late_w)
                n_late = jnp.sum(late_mask.astype(jnp.int32))
                mean_staleness = jnp.sum(
                    staleness * late_mask.astype(jnp.float32)
                ) / jnp.maximum(n_late.astype(jnp.float32), 1.0)
                # Fresh cohort = on-time clients only; late weights keep
                # the pre-deadline sizes, so a client contributes through
                # exactly one row.
                part_sizes = part_sizes * on_time.astype(part_sizes.dtype)
            total_size = jnp.sum(part_sizes)
            norm_w = part_sizes / jnp.maximum(total_size, 1e-12)
            if af is not None:
                on_time_count = jnp.sum((part_sizes > 0).astype(jnp.int32))

            aux = {}
            if materialize:
                client_params, new_state_k, train_metrics = train_clients(
                    global_params, state_k, x_k, y_k, m_k, client_keys,
                    lr_scale,
                )
                if compute_dtype is not None:
                    # Robust rules / Shapley consume the full stack; restore
                    # f32 so their statistics don't run at 8-bit mantissa
                    # (materializing cohorts are small by construction).
                    client_params = jax.tree_util.tree_map(
                        lambda p: p.astype(jnp.float32), client_params
                    )
                if self._client_eval_enabled:
                    # Per-client telemetry evaluates the raw LOCAL params —
                    # the reference's observable (each worker thread
                    # evaluates its own trained model BEFORE the quantized
                    # upload, fed_quant_worker.py:55-58) — not the payload-
                    # transformed upload. The eval program itself applies
                    # client_param_transform (post_round), matching the
                    # reference's QAT-instrumented eval forward exactly.
                    # For plain fed both are identities. Stored BEFORE
                    # upload corruption: the local model trained fine; the
                    # fault hits what the server receives.
                    aux["client_params_raw"] = client_params
                if failed is not None and fm.corrupts_upload:
                    client_params = fm.corrupt_stack(client_params, failed)
                if failed is not None and fm.freezes_client_state:
                    new_state_k = fm.freeze_failed_state(
                        failed, state_k, new_state_k
                    )
                if cs is not None:
                    # Same functions as the fused/bucketed chunks, applied
                    # to the already-resident stack at the same point
                    # (post-corruption, pre-payload) — the paths stay a
                    # differential pair for the stats too.
                    train_metrics = cs.add_upload_stats(
                        train_metrics, global_params, client_params
                    )
                client_params, payload_aux = self.process_client_payload(
                    client_params, payload_key
                )
                late_sum = None
                if af is not None:
                    # Same post-payload point as the fused path's late row
                    # (a late fed_quant client quantizes its own upload
                    # before it reaches the buffer).
                    late_sum = jax.tree_util.tree_map(
                        lambda p: jnp.tensordot(
                            late_w, p, axes=(0, 0),
                            preferred_element_type=jnp.float32,
                        ),
                        client_params,
                    )
                new_global = aggregate(
                    client_params, part_sizes, aggregation, cfg.trim_ratio
                )
                if aggregation != "mean" and not quorum:
                    # Robust rules promise a usable model even under
                    # poisoning; if EVERY client diverged in the same round
                    # (all candidates masked), keep the previous global
                    # instead of a NaN aggregate. The plain mean keeps
                    # propagate-NaN semantics (reference parity). With the
                    # quorum guard active this fallback is subsumed by the
                    # rejection logic below — which also RECORDS the event.
                    finite = all_finite(new_global)
                    new_global = jax.tree_util.tree_map(
                        lambda agg, prev: jnp.where(
                            finite, agg, prev.astype(agg.dtype)
                        ),
                        new_global, global_params,
                    )
                if keep_processed:
                    # Shapley's subset re-averaging consumes the processed
                    # stack. client_eval does NOT also store it — one
                    # resident stack, matching what
                    # _assert_client_stack_feasible budgets for.
                    aux["client_params"] = client_params
            else:
                plan = None
                if bucket_sizes is not None:
                    plan = _bucket_plan(x_k.shape[1] // cfg.batch_size)
                    if len(plan) <= 1:
                        # Uniform work: scheduling is a no-op; keep the
                        # plain path (bit-identical to scheduling-off).
                        plan = None
                if plan is not None:
                    agg_out, new_state_k, train_metrics = (
                        train_and_reduce_bucketed(
                            plan, global_params, state_k, x_k, y_k, m_k,
                            client_keys, norm_w, late_w, failed,
                            payload_key, lr_scale,
                        )
                    )
                else:
                    agg_out, new_state_k, train_metrics = train_and_reduce(
                        global_params, state_k, x_k, y_k, m_k, client_keys,
                        norm_w, late_w, failed, payload_key, lr_scale,
                    )
                if af is not None:
                    new_global, late_sum = agg_out
                else:
                    new_global = agg_out
                payload_aux = {}
            keep_round = total_size > 0
            if af is not None:
                # Staleness buffer (robustness/arrivals.py): insert this
                # round's late batch, fire the K-of-N trigger, mix the
                # buffered mean delta into the aggregate at its weight
                # share. A non-triggering round returns the fresh
                # aggregate through a bit-exact select.
                (new_global, buffer_applied, astate_ins,
                 astate_next) = af.absorb_and_apply(
                    async_state, global_params, new_global, total_size,
                    late_sum, b_tot, n_late, sim_duration,
                )
                # A buffer-only round (whole cohort late) is a real
                # update, not an empty round.
                keep_round = keep_round | buffer_applied
            # Empty effective cohort (all sampled clients have zero samples,
            # possible under extreme Dirichlet skew — or the whole cohort
            # dropped out / missed the deadline): keep the previous global
            # model, parity with fed_server.py:45-47.
            new_global = jax.tree_util.tree_map(
                lambda agg, prev: jnp.where(
                    keep_round, agg, prev.astype(agg.dtype)
                ),
                new_global, global_params,
            )
            if cs is not None:
                # [N, S] per-client stats (telemetry/client_stats.py):
                # the aggregate-delta probe uses the RAW round aggregate —
                # before the server optimizer, the downlink transform, and
                # any quorum rejection select — i.e. the same quantity the
                # clients' uploads averaged into.
                aux["client_stats"] = cs.stats_matrix(
                    train_metrics,
                    cs.probe_delta(global_params, new_global),
                )
                if cv is not None:
                    # Streaming valuation scores (telemetry/valuation.py):
                    # cosine-vs-aggregate x update-norm per cohort client,
                    # normalized to unit L1 — the in-program half of the
                    # estimator; the host folds in the server loss-delta
                    # and the exponential decay. Derived from the stats
                    # matrix above, so it shares the probe, the
                    # post-corruption measurement point, and the
                    # fused/bucketed/materializing-path parity for free.
                    aux["valuation_scores"] = cv.scores(aux["client_stats"])
            if quorum:
                # Quorum policy: a round is REJECTED — previous global
                # retained, the event recorded — when honest survivors fall
                # below min_survivors OR the aggregate is non-finite (the
                # plain mean otherwise NaN-propagates a corrupt upload into
                # the global model forever). Checked after the empty-cohort
                # fallback (an empty round is a survivor-floor event, not a
                # NaN event) and INSTEAD of the robust-rule finite guard,
                # which it subsumes; in-program jnp.where keeps the whole
                # round one XLA program (no host sync to decide).
                if failed is not None and departed is not None:
                    survived = survival & (~departed)
                elif failed is not None:
                    survived = survival
                elif departed is not None:
                    # Dynamic population, no failure model: departures
                    # alone can push a round below the quorum floor —
                    # the graceful-degradation contract.
                    survived = ~departed
                else:
                    survived = None
                survivor_count = (
                    jnp.sum(survived.astype(jnp.int32))
                    if survived is not None
                    else jnp.asarray(n_participants, jnp.int32)
                )
                finite = all_finite(new_global)
                rejected = (~finite) | (survivor_count < min_survivors)
                aux["survivor_count"] = survivor_count
                aux["round_rejected"] = rejected
            new_global, agg_aux = self.process_aggregated(new_global, agg_key)
            if quorum:
                # The rejection select runs AFTER process_aggregated so a
                # rejected round retains the previous global EXACTLY: the
                # round's input params already went through the downlink
                # transform last round (fed_quant re-quantizing the
                # "retained" model with fresh noise would move it).
                new_global = jax.tree_util.tree_map(
                    lambda agg, prev: jnp.where(
                        rejected, prev.astype(agg.dtype), agg
                    ),
                    new_global, global_params,
                )
            if af is not None:
                if quorum:
                    # A rejected round keeps its buffer INSERTS (the late
                    # uploads really arrived) but reverts any trigger/reset
                    # — the refused aggregate never consumed them; the
                    # trigger re-fires next round.
                    new_async_state = jax.tree_util.tree_map(
                        lambda ins, nxt: jnp.where(rejected, ins, nxt),
                        astate_ins, astate_next,
                    )
                    applied_eff = buffer_applied & ~rejected
                else:
                    new_async_state = astate_next
                    applied_eff = buffer_applied
                # The buffer carry rides aux: the host loop pops it and
                # feeds it back as the next round's async_state operand.
                aux["async_state"] = new_async_state
                aux.update({
                    "on_time_count": on_time_count,
                    "late_count": n_late,
                    "buffer_count": new_async_state["buf_count"],
                    "buffer_applied": applied_eff,
                    "mean_staleness": mean_staleness,
                    "sim_duration": sim_duration,
                    "sim_duration_sync": sim_duration_sync,
                    "sim_clock": new_async_state["clock"],
                })
            aux.update({
                "client_loss": train_metrics["loss"],
                "client_accuracy": train_metrics["accuracy"],
                "mean_client_loss": jnp.mean(train_metrics["loss"]),
                **payload_aux,
                **agg_aux,
            })
            if "model_counts" in train_metrics:
                # A model's own counters (an expert layer's routing
                # counts), summed over the cohort: they ride the round's
                # one metric fetch into the span recorder's counters.
                aux["model_counts"] = jax.tree_util.tree_map(
                    lambda c: jnp.sum(c, axis=0),
                    train_metrics["model_counts"],
                )
            return new_global, new_state_k, aux

        def round_fn(global_params, client_state, cx, cy, cmask, sizes, key,
                     lr_scale=1.0, async_state=None):
            if af is not None and async_state is None:
                # Trace-time wiring check: the simulator owns the buffer
                # carry; a direct caller forgetting it would otherwise
                # train with a silently-fresh buffer every round.
                raise ValueError(
                    "async_mode='on' round program needs the async_state "
                    "operand (AsyncFederation.init_state)"
                )
            keys = split_round_key(key)
            idx = None
            if n_participants == n_clients:
                state_k, x_k, y_k, m_k = client_state, cx, cy, cmask
                part_sizes = sizes
            else:
                # Client sampling: train only the sampled cohort (fixed size
                # -> one compilation); non-participants keep their state and
                # contribute nothing to aggregation. The draw is the ONE
                # sampler implementation (ops/sampling.py) shared with the
                # host replay in cohort_indices — exact = the pre-feature
                # choice(replace=False), hashed = the O(cohort) keyed draw.
                idx = draw_cohort(
                    keys[0], n_clients, n_participants,
                    getattr(cfg, "participation_sampler", "exact").lower(),
                )
                state_k = cohort_take(client_state, idx)
                x_k, y_k, m_k = (
                    cohort_take(cx, idx),
                    cohort_take(cy, idx),
                    cohort_take(cmask, idx),
                )
                part_sizes = cohort_take(sizes, idx)
            new_global, new_state_k, aux = cohort_round(
                global_params, state_k, x_k, y_k, m_k, part_sizes, idx,
                key, keys, lr_scale, async_state,
            )
            if idx is not None:
                # Sampled cohort indices: third-party post_round attribution
                # and the host loop's cohort_hash resume-determinism
                # telemetry.
                aux["participants"] = idx
                new_state = cohort_scatter(client_state, idx, new_state_k)
            else:
                new_state = new_state_k
            return new_global, new_state, aux

        if not streamed:
            return round_fn

        # Dynamic population (config.population; robustness/population.py):
        # a trace-time gate like fm/cs/af — 'static' (the default)
        # compiles the exact pre-feature streamed program; 'dynamic'
        # adds the per-cohort ``departed`` operand (validated streamed-
        # only, so the resident entry never grows it).
        dyn = (
            getattr(cfg, "population", "static") or "static"
        ).lower() == "dynamic"

        def round_fn_streamed(global_params, state_k, x_k, y_k, m_k,
                              part_sizes, idx, key, lr_scale=1.0,
                              async_state=None, departed=None,
                              draw_pos=None):
            """Streamed calling convention (base.Algorithm docstring): the
            cohort slice arrives pre-gathered from the host shard store,
            ``idx`` is its true client ids (None = whole population), and
            the post-round cohort state is RETURNED, not scattered — the
            streamer writes it back into the host store. The round key is
            split exactly as in the resident program (part_key is
            consumed by the host's cohort replay instead of an in-program
            choice), so every downstream draw is unchanged. ``departed``
            (population='dynamic') is the host registration stream's
            this-round departure mask over the cohort."""
            if af is not None and async_state is None:
                raise ValueError(
                    "async_mode='on' round program needs the async_state "
                    "operand (AsyncFederation.init_state)"
                )
            if dyn and departed is None:
                # Trace-time wiring check, like the async one above: the
                # simulator owns the registration stream; a direct
                # caller forgetting the mask would silently train
                # departed clients at full weight.
                raise ValueError(
                    "population='dynamic' round program needs the "
                    "departed operand "
                    "(PopulationModel.cohort_departed_mask)"
                )
            keys = split_round_key(key)
            new_global, new_state_k, aux = cohort_round(
                global_params, state_k, x_k, y_k, m_k, part_sizes, idx,
                key, keys, lr_scale, async_state,
                departed=departed if dyn else None,
                draw_pos=draw_pos,
            )
            if idx is not None:
                aux["participants"] = idx
            return new_global, new_state_k, aux

        return round_fn_streamed

    def make_valuation_audit_fn(self, apply_fn, optimizer, preprocess=None):
        """Build the valuation auditor's cohort-stack replay program.

        ``audit_stack(global_params, x_k, y_k, m_k, client_keys,
        payload_key, lr_scale) -> [cohort, ...] payload-processed
        params`` — the EXACT per-client uploads the round aggregated,
        re-materialized for the truncated GTG audit walk
        (telemetry/valuation.py). The replay trains the cohort from the
        round's pre-round global params with the same per-client keys
        (``round_key_splits``' train_key fan-out — the caller derives
        them host-side) and the same local-train build knobs; the only
        difference from the live round is ``collect_stats=False``, which
        changes metric outputs, never the trained params (the PR 4
        off-gate contract). Audit preconditions (plain ``fed`` only, no
        faults, no async, no persistent client state —
        config.validate()) keep the replay this simple AND exact:
        ``process_client_payload`` is fed's identity here (fed_quant is
        refused — its live fused path quantizes with per-chunk payload
        keys that a whole-stack replay cannot reproduce), so the
        replayed stack is bit-for-bit the uploads the round aggregated
        on single-device runs. One documented softening under
        single-host ``mesh_devices > 1`` (composes since PR 14): the
        LIVE round trains the cohort client-axis-sharded while this
        replay runs at full width on one placement, and per-device
        batch tiling can move trained params by last-ulp amounts — far
        below the audit walk's Monte-Carlo noise (the graded-
        differential Spearman floor is pinned under mesh,
        tests/test_gtg_mesh.py), but "bit-for-bit" is a serial-run
        statement.
        """
        from distributed_learning_simulator_tpu.ops.augment import get_augment

        cfg = self.config
        compute_dtype = None
        if getattr(cfg, "local_compute_dtype", "float32") == "bfloat16":
            compute_dtype = jnp.bfloat16
        local_train = make_local_train_fn(
            apply_fn,
            optimizer,
            local_epochs=cfg.epoch,
            batch_size=cfg.batch_size,
            param_transform=self.client_param_transform(),
            reset_optimizer=cfg.reset_client_optimizer,
            preprocess=preprocess,
            augment=get_augment(cfg.augment),
            compute_dtype=compute_dtype,
            collect_stats=False,
        )
        vtrain = jax.vmap(local_train, in_axes=(None, 0, 0, 0, 0, 0, None))
        chunk = cfg.client_chunk_size

        def audit_stack(global_params, x_k, y_k, m_k, client_keys,
                        payload_key, lr_scale=1.0):
            if chunk is None or chunk >= client_keys.shape[0]:
                cp, _, _ = vtrain(
                    global_params, None, x_k, y_k, m_k, client_keys,
                    lr_scale,
                )
            else:
                # Same memory envelope as the round itself: chunk clients
                # in flight (lax.map's batch_size), never the whole
                # cohort's training transients at once.
                def one_client(args):
                    xi, yi, mi, k = args
                    cp_i, _, _ = local_train(
                        global_params, None, xi, yi, mi, k, lr_scale
                    )
                    return cp_i

                cp = jax.lax.map(
                    one_client, (x_k, y_k, m_k, client_keys),
                    batch_size=chunk,
                )
            if compute_dtype is not None:
                # The subset evaluator consumes the stack like the
                # materializing round path does: f32.
                cp = jax.tree_util.tree_map(
                    lambda p: p.astype(jnp.float32), cp
                )
            cp, _ = self.process_client_payload(cp, payload_key)
            return cp

        return audit_stack

    def client_param_transform(self):
        """Param transform inside the client loss (QAT hook; None here)."""
        return None

    # ---- server optimizer (FedOpt family; exceeds the reference) ----------
    def make_server_update(self):
        """Optional server-side optimizer step applied to the round aggregate.

        Returns ``(init_fn, update_fn)`` or ``None`` (plain FedAvg — the
        reference's fixed behavior, fed_server.py:81-84, where the aggregate
        becomes the next global model directly). With a server optimizer the
        pseudo-gradient ``prev_global - aggregate`` is fed to optax:
        sgd+momentum = FedAvgM, adam = FedAdam (Reddi et al., "Adaptive
        Federated Optimization"). sgd(lr=1, momentum=0) reduces exactly to
        FedAvg: ``prev - 1.0 * (prev - agg) = agg``.
        """
        cfg = self.config
        name = cfg.server_optimizer_name.lower()
        if name in ("none", ""):
            return None
        if name == "sgd":
            tx = optax.sgd(
                cfg.server_learning_rate, momentum=cfg.server_momentum or None
            )
        elif name == "adam":
            tx = optax.adam(cfg.server_learning_rate)
        else:  # pre-validated in ExperimentConfig.validate
            raise ValueError(
                f"unknown server optimizer {name!r}; known: none, sgd, adam"
            )

        def update(prev_global, aggregate, opt_state, rejected=None):
            pseudo_grad = jax.tree_util.tree_map(
                lambda p, a: (p - a.astype(p.dtype)), prev_global, aggregate
            )
            updates, new_opt_state = tx.update(
                pseudo_grad, opt_state, prev_global
            )
            stepped = optax.apply_updates(prev_global, updates)
            if rejected is None:
                return stepped, new_opt_state
            # Quorum rejection (the simulator passes the round's rejected
            # flag whenever the round program produced one): a rejected
            # round's pseudo-gradient is 0, but a momentum trace / Adam
            # moments from PRIOR rounds would still move the params and
            # advance the optimizer state — "previous global retained"
            # must mean exactly that, so both are frozen.
            params = jax.tree_util.tree_map(
                lambda s, p: jnp.where(rejected, p, s), stepped, prev_global
            )
            frozen_opt = jax.tree_util.tree_map(
                lambda n, o: jnp.where(rejected, o, n),
                new_opt_state, opt_state,
            )
            return params, frozen_opt

        return tx.init, update
