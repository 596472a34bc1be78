"""Asynchronous federation: device-side arrival model, deadline rounds,
and buffered staleness-weighted aggregation — all INSIDE the jitted round.

Every algorithm in this repo was synchronous-round: the straggler fault
(:mod:`robustness.faults`) simulates slow clients, but the server could
only wait or drop them — a straggler's upload was discarded forever, the
opposite of graceful degradation. FedML Parrot (PAPERS.md) makes
heterogeneity-aware scheduling a simulator primitive; FedBuff-style
buffered aggregation (Nguyen et al.) is the standard server answer to
stragglers. This module brings both to the one-XLA-program round design,
with BlazeFL's fast-and-*deterministic* bar: ``async_mode='off'`` (the
default) compiles the exact pre-feature program, and
``round_deadline=inf`` makes the compiled *async* program bit-identical
to synchronous FedAvg (tests/test_async.py).

Design, mirroring :class:`~robustness.faults.FailureModel`:

* :class:`AsyncFederation` is built from config (``async_mode='off'``
  returns None, and every call site gates at TRACE time on that).
* **Arrival model** (``arrival_model={bimodal,lognormal}``): each
  client has a persistent speed factor drawn from its TRUE client index
  under ``arrival_seed`` — an ``arrival_slow_fraction`` share of the
  population is ``arrival_slow_factor``× slower (the 80/20 fast/slow
  knob) — times a per-round jitter drawn from the ROUND key via
  ``fold_in`` (uniform [0.5, 1.5) for ``bimodal``,
  ``exp(sigma · N(0,1))`` for ``lognormal``). The fold_in-decoupled
  stream means activating arrivals re-rolls NOTHING else: cohort
  sampling, failure draws, training batches and payload keys are
  untouched (the same discipline as ``failure_seed``).
* **Deadline rounds**: clients whose latency is at most
  ``round_deadline`` contribute *fresh*, exactly like synchronous
  FedAvg over the on-time sub-cohort. The server closes the round at
  ``min(round_deadline, max latency)`` of simulated time — the advancing
  simulated wall-clock whose sum, against the synchronous counterfactual
  ``max latency`` (wait for everyone), is the run's
  ``async_speedup_ratio``.
* **Staleness buffer**: a late upload's *delta* (vs the global model it
  trained from) lands in a device-resident accumulator with weight
  ``size · (1 + s)^(-staleness_alpha)``, where the staleness ``s`` is
  how many rounds late the upload arrives (``ceil(latency/deadline) -
  1``; a fault-routed straggler is at least 1). The discount is fixed at
  insertion — the buffer holds ONE param-sized tree regardless of how
  many uploads it absorbs, so buffer memory never scales with
  ``async_buffer_size`` or the model. When the buffered-upload count
  reaches ``async_buffer_size`` (FedBuff's K-of-N trigger), the
  buffered mean delta is applied alongside that round's fresh aggregate,
  weighted by its share of the combined weight, and the buffer resets.
  Stale deltas applied to a moved global model are the standard
  async-FL semantics (the staleness the discount pays for).
* A non-finite late batch (a ``corrupt_nan`` client missing the
  deadline) is dropped at insertion (:func:`~robustness.faults.
  all_finite` guard) — one poisoned upload must not brick the buffer
  for the rest of the run. A quorum-rejected round keeps its inserts
  but reverts any trigger/reset (the late arrivals really arrived; the
  poisoned aggregate is what was refused).

Composition matrix, semantics and the acceptance evidence:
docs/ROBUSTNESS.md § Asynchronous federation.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from distributed_learning_simulator_tpu.robustness.faults import all_finite

ARRIVAL_MODES = ("none", "bimodal", "lognormal")
ASYNC_MODES = ("off", "on")

#: fold_in tag separating the arrival stream from every other consumer
#: of the round key (no other module folds the raw round key).
_ARRIVAL_STREAM = 0x61727276  # "arrv"


def staleness_discount(staleness, alpha: float):
    """Polynomial staleness discount ``(1 + s)^(-alpha)`` (FedBuff /
    Xie et al. "Asynchronous Federated Optimization"): ``alpha=0`` keeps
    late updates at full weight, larger ``alpha`` trusts them less."""
    return (1.0 + staleness) ** (-alpha)


@dataclass(frozen=True)
class AsyncFederation:
    """Static (trace-time) async-federation configuration; per-round
    draws and the buffer update are pure functions of the round key and
    the carried buffer state, so one compiled round program serves every
    round."""

    arrival_model: str
    slow_fraction: float
    slow_factor: float
    sigma: float
    seed: int
    deadline: float
    buffer_size: int
    alpha: float

    @classmethod
    def from_config(cls, config) -> "AsyncFederation | None":
        """None when ``async_mode='off'`` (the default) — callers gate
        every trace-time branch on that, so synchronous runs compile the
        exact pre-feature program."""
        mode = (getattr(config, "async_mode", "off") or "off").lower()
        if mode == "off":
            return None
        if mode not in ASYNC_MODES:
            raise ValueError(
                f"unknown async_mode {mode!r}; known: "
                + ", ".join(ASYNC_MODES)
            )
        arrival = getattr(config, "arrival_model", "none") or "none"
        if arrival == "none":
            raise ValueError(
                "async_mode='on' needs an arrival model to order uploads "
                "against round_deadline; set arrival_model='bimodal' or "
                "'lognormal'"
            )
        if arrival not in ARRIVAL_MODES:
            raise ValueError(
                f"unknown arrival_model {arrival!r}; known: "
                + ", ".join(ARRIVAL_MODES)
            )
        return cls(
            arrival_model=arrival,
            slow_fraction=float(getattr(config, "arrival_slow_fraction", 0.2)),
            slow_factor=float(getattr(config, "arrival_slow_factor", 8.0)),
            sigma=float(getattr(config, "arrival_sigma", 0.5)),
            seed=int(getattr(config, "arrival_seed", 0)),
            deadline=float(getattr(config, "round_deadline", float("inf"))),
            buffer_size=int(getattr(config, "async_buffer_size", 8)),
            alpha=float(getattr(config, "staleness_alpha", 0.5)),
        )

    # ---- jit-side draws ----------------------------------------------------
    def speed_factors(self, client_ids):
        """Persistent ``[n]`` per-client slowdown factors (1.0 for the
        fast population, ``slow_factor`` for the slow one). Keyed by the
        TRUE client index under ``arrival_seed`` only — a client keeps
        its speed across rounds, participation sampling, and resume."""
        k = jax.random.fold_in(jax.random.key(self.seed), _ARRIVAL_STREAM)
        u = jax.vmap(
            lambda i: jax.random.uniform(jax.random.fold_in(k, i))
        )(client_ids)
        return jnp.where(
            u < self.slow_fraction,
            jnp.float32(self.slow_factor),
            jnp.float32(1.0),
        )

    def speed_table(self, n_clients: int):
        """The whole population's :meth:`speed_factors` as one ``[n]``
        table. Built EAGERLY once at round-fn construction and closed
        over as a constant: the factors depend only on ``arrival_seed``
        and the client index, so recomputing the per-client fold_in
        chains inside the compiled round
        would be pure waste — the round program just gathers from the
        table."""
        return self.speed_factors(jnp.arange(n_clients))

    def draw_latency(self, key, client_ids, speeds=None):
        """``[n]`` simulated upload latencies for one round's cohort
        (speed factor × per-round jitter, in ``round_deadline`` units).
        ``speeds`` — the cohort's rows of :meth:`speed_table`; derived
        from ``client_ids`` when omitted (same values either way).

        ``fold_in(key, tag/seed)`` decouples the arrival stream from
        every other consumer of the round key: the splits the
        synchronous program draws are untouched, which is what makes the
        ``round_deadline=inf`` degenerate case bit-identical to sync —
        sampling, failure draws, and batch shuffles included.
        """
        k = jax.random.fold_in(
            jax.random.fold_in(key, _ARRIVAL_STREAM), self.seed
        )
        n = client_ids.shape[0]
        if self.arrival_model == "bimodal":
            jitter = jax.random.uniform(k, (n,), minval=0.5, maxval=1.5)
        else:  # lognormal (from_config validated the name set)
            jitter = jnp.exp(self.sigma * jax.random.normal(k, (n,)))
        if speeds is None:
            speeds = self.speed_factors(client_ids)
        return speeds * jitter

    def classify(self, latency, forced_late=None):
        """Split one round's cohort against the deadline.

        Returns ``(on_time, staleness, discount, eff_latency)``: a bool
        ``[n]`` mask, the integer-valued f32 staleness (rounds late:
        ``ceil(latency/deadline) - 1``, at least 1 for ``forced_late``
        clients — the straggler fault routed into the buffer), the
        per-client :func:`staleness_discount`, and the EFFECTIVE
        latencies: a fault-routed straggler's upload is delayed one full
        deadline past its drawn arrival, so the simulated clock
        (:meth:`durations`) pays for the very stragglers the routing
        buffers — staleness and clock stay consistent. At
        ``deadline=inf`` there is no deadline to miss: non-forced
        clients are on time at staleness 0, forced clients keep their
        drawn latency (finite telemetry) with staleness floored at 1.
        """
        if forced_late is not None and math.isfinite(self.deadline):
            latency = jnp.where(
                forced_late, latency + jnp.float32(self.deadline), latency
            )
        on_time = latency <= self.deadline
        s = jnp.maximum(jnp.ceil(latency / self.deadline) - 1.0, 0.0)
        if forced_late is not None:
            on_time = on_time & ~forced_late
            s = jnp.where(forced_late, jnp.maximum(s, 1.0), s)
        return on_time, s, staleness_discount(s, self.alpha), latency

    def durations(self, latency):
        """Simulated round durations ``(async, sync)``: the deadline
        server closes at ``min(deadline, max latency)``; the synchronous
        counterfactual waits for the whole cohort (``max latency`` — the
        reference's blocking barrier, idealized to terminate)."""
        slowest = jnp.max(latency)
        return jnp.minimum(slowest, jnp.float32(self.deadline)), slowest

    # ---- buffer carry ------------------------------------------------------
    def init_state(self, global_params) -> dict:
        """Round-0 buffer state: one f32 param-sized accumulator of
        discounted late deltas plus three scalars. This dict is the
        round program's async carry — handed from round to round by the
        host loop, checkpointed, and restored on resume like every other
        piece of round state."""
        return {
            "buf_sum": jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), global_params
            ),
            "buf_weight": jnp.zeros((), jnp.float32),
            "buf_count": jnp.zeros((), jnp.int32),
            "clock": jnp.zeros((), jnp.float32),
        }

    def absorb_and_apply(self, state, global_params, fresh_agg, a_tot,
                         late_sum, b_tot, n_late, sim_duration):
        """One round's buffer step: insert the late batch, fire the
        K-of-N trigger, produce the round's aggregate.

        Inputs: ``fresh_agg`` — the on-time cohort's aggregate, computed
        with the synchronous formula over on-time weights summing to
        ``a_tot``; ``late_sum`` — the discounted weighted SUM of late
        clients' (payload-processed) params with total weight ``b_tot``
        over ``n_late`` uploads. ``late_sum - b_tot·g`` is the late
        batch's delta vs this round's global — stale by construction
        when applied later.

        Returns ``(new_global, applied, state_inserted, state_next)``:
        ``new_global`` is ``fresh_agg`` untouched (bit-exact
        select) unless the trigger fired, in which case the buffered
        mean delta joins at its ``buf_weight/(a_tot + buf_weight)``
        share; ``state_inserted`` keeps the inserts without the reset
        (what a quorum-REJECTED round must carry forward — the late
        arrivals really arrived); ``state_next`` is the normal
        post-round state (reset when applied). A non-finite late batch
        is dropped whole at insertion so the buffer stays finite.
        """
        g32 = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.float32), global_params
        )
        late_delta = jax.tree_util.tree_map(
            lambda ls, g: ls - b_tot * g, late_sum, g32
        )
        # Coarse by design: one NaN late upload drops the whole round's
        # late batch (per-upload finiteness would need per-client
        # reductions the fused path avoids); the honest late clients
        # lose one insert, the buffer survives the run.
        ins_ok = all_finite(late_delta) & (n_late > 0)
        buf_sum = jax.tree_util.tree_map(
            lambda b, d: b + jnp.where(ins_ok, d, 0.0),
            state["buf_sum"], late_delta,
        )
        buf_weight = state["buf_weight"] + jnp.where(ins_ok, b_tot, 0.0)
        buf_count = state["buf_count"] + jnp.where(
            ins_ok, n_late, jnp.int32(0)
        )
        applied = buf_count >= self.buffer_size
        a_f = a_tot.astype(jnp.float32)
        beta = jnp.where(
            applied, buf_weight / jnp.maximum(a_f + buf_weight, 1e-12), 0.0
        )
        a_pos = a_f > 0
        combined = jax.tree_util.tree_map(
            # Fresh delta zeroed (not multiplied) when the on-time cohort
            # is empty: 0 * NaN would poison a buffer-only round.
            lambda g, f, b: (
                g
                + (1.0 - beta)
                * jnp.where(a_pos, f.astype(jnp.float32) - g, 0.0)
                + beta * (b / jnp.maximum(buf_weight, 1e-12))
            ),
            g32, fresh_agg, buf_sum,
        )
        new_global = jax.tree_util.tree_map(
            lambda f, c: jnp.where(applied, c.astype(f.dtype), f),
            fresh_agg, combined,
        )
        clock = state["clock"] + sim_duration
        state_inserted = {
            "buf_sum": buf_sum,
            "buf_weight": buf_weight,
            "buf_count": buf_count,
            "clock": clock,
        }
        state_next = {
            "buf_sum": jax.tree_util.tree_map(
                lambda b: jnp.where(applied, jnp.zeros_like(b), b), buf_sum
            ),
            "buf_weight": jnp.where(applied, 0.0, buf_weight),
            "buf_count": jnp.where(applied, jnp.int32(0), buf_count),
            "clock": clock,
        }
        return new_global, applied, state_inserted, state_next
