"""Algorithm strategy interface.

What survives of the reference's server/worker class split
(reference servers/*.py + workers/*.py + factory.py:14-35): an algorithm is a
strategy object that

  * builds a jitted **round function** — the whole synchronous round
    (broadcast -> N local trainings -> gather -> aggregate) as ONE XLA
    program over client-stacked arrays; and
  * optionally runs a host-side **post_round** hook — for work that is
    genuinely data-dependent control flow (Shapley convergence loops,
    reference GTG_shapley_value_server.py:36) or pure logging/persistence.

The reference's template-method hooks ``_process_client_parameter`` /
``_process_aggregated_parameter`` (servers/fed_server.py:38-42) survive as
the jax-level hooks ``process_client_payload`` / ``process_aggregated`` on
:class:`~distributed_learning_simulator_tpu.algorithms.fedavg.FedAvg`.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

import jax


@dataclass
class RoundContext:
    """Everything a host-side post_round hook may need for one round."""

    round_idx: int  # 0-based
    global_params: Any  # aggregated params after this round
    prev_global_params: Any  # global params before this round
    sizes: Any  # [n_clients] aggregation weights
    aux: dict  # round_fn diagnostics (may hold 'client_params')
    metrics: dict  # server-side eval of global_params {'loss','accuracy'}
    prev_metrics: dict | None  # eval of prev_global_params (previous round)
    eval_batches: tuple  # (xb, yb, mb) padded test set on device
    log_dir: str | None
    extra: dict = field(default_factory=dict)


class Algorithm:
    """Base strategy. Subclasses set ``name`` (registry key, parity with
    reference factory.py:14-35) and implement ``make_round_fn``."""

    name: str = ""
    # Public contract: truthy ``keep_client_params`` — set at CLASS level
    # (Shapley) or on an INSTANCE (third-party subclasses) — makes the round
    # program materialize every client's parameters and expose the
    # payload-PROCESSED stack as ``aux['client_params']`` for post_round.
    # (FedAvg's client_eval telemetry does NOT use this flag: it requests
    # the RAW pre-payload stack through a private channel, so enabling it
    # never changes what ``aux['client_params']`` holds.)
    keep_client_params: bool = False
    # Whether the host round loop may defer this algorithm's metric fetch +
    # post_round by one round (hides device->host latency behind the next
    # round's compute). Safe when post_round is analytic/logging-only; the
    # Shapley algorithms set False — their post_round drives data-dependent
    # subset evaluation that must see the round's metrics synchronously.
    supports_round_pipelining: bool = True
    # Whether the host loop may donate the global model to the round
    # program, which then writes the new global into the old one's buffer
    # (one f32 copy of the model fewer on the device). The loop does so
    # only where it is not pipelined and nothing else it runs takes the
    # previous global; then ``RoundContext.prev_global_params`` is None.
    # True says post_round does not read it. Conservative default False;
    # FedAvg opts in, the Shapley servers (subset means are taken around
    # the previous global) opt out again.
    supports_global_donation: bool = False
    # Whether round_fn accepts the optional trailing ``lr_scale`` operand
    # (config.lr_schedule): the simulator passes it only when a schedule
    # is active AND the algorithm declares support — an algorithm without
    # the operand still works with the constant default.
    supports_lr_schedule: bool = False
    # Whether the algorithm's round program can run under
    # ``config.client_residency='streamed'`` (data/residency.py +
    # parallel/streaming.py): per-client arrays live in a host shard
    # store and the round fn takes the STREAMED calling convention —
    # ``round_fn(global_params, state_k, x_k, y_k, m_k, part_sizes, idx,
    # key[, lr_scale][, async_state])`` where the cohort slices are
    # already-gathered operands and ``idx`` is the cohort's true client
    # ids (None when the cohort is the whole population). Conservative
    # default False — the simulator refuses with the cause; FedAvg
    # builds the streamed program natively, sign_SGD adapts its
    # full-population round via ``adapt_full_cohort_streamed``, the
    # Shapley servers refuse (their subset re-evaluation assumes a
    # resident stack).
    supports_streamed_residency: bool = False
    # Whether the round program implements asynchronous federation
    # (config.async_mode='on'; robustness/arrivals.py): deadline rounds,
    # the staleness buffer carried as round state, and the extra
    # ``async_state`` round_fn operand. Conservative default False — the
    # simulator refuses async_mode='on' with the cause instead of
    # silently running the algorithm synchronously; the FedAvg family
    # opts in (sign_SGD's shared-vote round has no parameter-space
    # buffer to hold late updates; the Shapley servers refuse in their
    # constructors — subset utilities assume a synchronous cohort).
    supports_async: bool = False
    # Whether the algorithm's post_round subset evaluation partitions its
    # vmapped model-batch axis over a single-host mesh (mesh_devices > 1;
    # algorithms/shapley.eval_mesh_devices + _SubsetEvaluator). A
    # CAPABILITY flag, not a gate: False just means post_round ignores
    # the mesh (the round program's client-axis sharding is independent
    # of it). The Shapley servers set True — their subset utilities are
    # independent, so sharding the evaluation batch is pure throughput,
    # bit-identical to the serial walk by construction.
    shards_subset_eval: bool = False

    def __init__(self, config):
        self.config = config

    def check_cohort(self, n_clients: int) -> None:
        """Validate the ACTUAL client count before any training runs.

        Called with the true ``n_clients`` (which a caller-supplied
        ``ClientData`` may make different from ``config.worker_number``)
        from every execution path's build step: the simulator calls it
        right before building the round fn on the vmap path (so every
        algorithm is covered regardless of its ``make_round_fn``
        inheritance; ``FedAvg.make_round_fn`` also calls it for direct
        library users), and the threaded runner before its pool spawns. The
        constructor can only see ``worker_number``, so count-dependent
        feasibility checks (exact Shapley's 2^N bound, GTG's permutation
        cap) live here and merely warn at construction."""

    @property
    def materializes_client_stack(self) -> bool:
        """Whether the round program holds the full [n_clients, params]
        stack resident (drives the simulator's up-front feasibility check;
        FedAvg widens this with its client_eval / robust-aggregation
        materializers)."""
        return bool(self.keep_client_params)

    # ---- jit side ----------------------------------------------------------
    def make_round_fn(
        self, apply_fn: Callable, optimizer, n_clients: int,
        preprocess: Callable | None = None,
        client_sizes=None,
    ) -> Callable:
        """Return ``round_fn(global_params, client_state, cx, cy, cmask,
        sizes, key[, lr_scale]) -> (new_global, new_client_state, aux)``.

        ``client_sizes`` (optional host numpy ``[n_clients]`` of real
        per-client sample counts) enables STATIC size-aware work
        scheduling where the algorithm supports it (FedAvg fused path,
        config.bucket_client_work); pass None when the client axis is
        sharded over a mesh (the static regrouping would fight the
        sharding layout) or when counts aren't known up front.
        ``client_sizes`` is captured at BUILD time into the static slice
        plan, while aggregation weights use the per-round ``sizes``
        operand — the two must describe the same clients. Mutating the
        client data (e.g. ``ClientData.override_client``) after building
        the round fn leaves a stale plan that silently truncates any
        client grown past its group's step budget: inject data BEFORE
        construction, as ``run_simulation`` and ``simulator_heterogeneous``
        do (ADVICE r4).

        ``client_state`` is whatever per-client state persists across rounds
        (optimizer/momentum buffers) as a client-stacked pytree; ``aux`` is a
        dict of diagnostics (device arrays). ``lr_scale`` (a traced f32
        scalar, default 1.0) is passed only when ``supports_lr_schedule``
        is True and a non-constant ``config.lr_schedule`` is active.
        """
        raise NotImplementedError

    def local_steps_unrolled(self, shard_size: int) -> int:
        """How many local steps this algorithm's round program holds
        unrolled for shards of ``shard_size`` samples; 0 where the local
        steps stay a loop (parallel/engine.UNROLL_MAX_LOCAL_STEPS). The
        run's span recorder keeps it as a counter of the same name."""
        return 0

    def init_client_state(self, optimizer, global_params, n_clients):
        """Initial per-client persistent state (client-stacked pytree).

        None when client optimizers reset every round (the default): no
        state persists, and carrying a per-client optimizer-state pytree at
        1000-client scale would cost a model-size buffer per client.
        """
        if getattr(self.config, "reset_client_optimizer", True):
            return None
        return jax.vmap(lambda _: optimizer.init(global_params))(
            jax.numpy.arange(n_clients)
        )

    def make_server_update(self):
        """Optional server-side optimizer: ``(init_fn, update_fn)`` or None.

        See FedAvg.make_server_update (FedOpt family). None (the default)
        means the round aggregate becomes the next global model unchanged.
        """
        return None

    # ---- streamed residency (config.client_residency='streamed') -----------
    def cohort_indices(self, round_key, n_clients: int, alive=None,
                       n_participants=None):
        """Host-replay of the round program's cohort draw.

        ``alive``/``n_participants`` are the dynamic-population hooks
        (``population='dynamic'``, robustness/population.py): a draw
        over the current registered index space with departed indices
        masked, at the pinned startup cohort size. Algorithms that
        support dynamic populations honor them (FedAvg); the default
        whole-population replay ignores them.

        Under streamed residency the host must know WHICH clients round
        ``round_key`` trains BEFORE dispatch (to gather their slice from
        the shard store — and to prefetch the next dispatch's slice while
        this one computes). The contract: given the same ``round_key``
        the host loop hands the round program, return exactly the client
        ids the RESIDENT program would draw in-program, as a host numpy
        array — or None when the cohort is the whole population (no
        sampling). The caller runs this on the CPU backend; jax PRNG
        values are backend-deterministic, which is what makes the replay
        exact (the PR 2/PR 6 round-key-chain discipline).
        """
        return None

    def gather_client_state(self, store, idx):
        """Cohort slice of the host store's persistent per-client state.

        The streamed-residency mirror of the resident program's
        in-program state gather (ops/cohort.cohort_take). The default
        delegates to the store's numpy index math; algorithms with
        exotic state layouts may override.
        """
        return store.gather_state(idx)

    def scatter_client_state(self, store, idx, cohort_state) -> None:
        """Write post-round cohort state back into the host store.

        Mirror of ops/cohort.cohort_scatter; called with HOST (numpy)
        values — the streamer fetches device state before scattering.
        """
        store.scatter_state(idx, cohort_state)

    # ---- host side ---------------------------------------------------------
    def prepare(self, apply_fn, eval_fn) -> None:
        """One-time setup after the engine is built (e.g. jit subset-eval)."""

    def post_round(self, ctx: RoundContext) -> dict:
        """Host-side per-round hook; returns extra metrics to record/log."""
        return {}


def adapt_full_cohort_streamed(round_fn):
    """Wrap a resident-convention round fn into the streamed convention.

    For algorithms whose cohort is always the whole population
    (sign_SGD: the per-step vote synchronizes everyone), the streamed
    operands ARE the full arrays and the conventions differ only by the
    ``idx`` operand — always None here — sitting before the key.
    """

    def streamed_fn(global_params, state_k, x_k, y_k, m_k, part_sizes, idx,
                    key, *args, **kwargs):
        assert idx is None, "full-cohort streamed round fn got a cohort index"
        return round_fn(
            global_params, state_k, x_k, y_k, m_k, part_sizes, key,
            *args, **kwargs
        )

    return streamed_fn
