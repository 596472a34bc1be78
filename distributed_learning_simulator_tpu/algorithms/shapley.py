"""Shapley-value contribution scoring: exact multi-round + GTG Monte-Carlo.

Replaces the reference's three Shapley servers (servers/shapley_value_server.py,
servers/multiround_shapley_value_server.py, servers/GTG_shapley_value_server.py).
Both algorithms run FedAvg rounds and then score each client's contribution to
the round's test metric.

TPU-first transformation (SURVEY 3.4): the reference evaluates one Python
subset at a time — a weighted average + a full test inference per subset
(multiround_shapley_value_server.py:34-40). Here a subset is a fixed-shape 0/1
mask; ``subset_weighted_mean`` is an einsum over (mask x client-params), and a
*batch* of subsets evaluates under one ``vmap`` — 2^N model materializations +
test inferences fused into chunked batched XLA calls.

Reference defects fixed, not replicated:
  * ``round_trunc_threshold`` is actually plumbed through config (the
    reference reads it from kwargs that factory.py:21-22 never passes,
    SURVEY 2.1#9).
  * GTG's contribution records are appended as *copies* — the reference
    appends the same mutable list N times per permutation, skewing both the
    convergence test and the final average (SURVEY 2.1#10).
  * GTG prefix evaluation is batched: a permutation's prefixes are fetched
    in fused blocks of ``_PREFIX_BLOCK`` (memoized), and the walk stops
    requesting blocks once eps-truncated — the reference's lazy skip
    semantics at a fraction of its N-sequential-host-round-trips cost.
    ``metric_<round>.pkl`` therefore holds only the prefixes actually
    evaluated (as the reference's lazy walk does), not every prefix.
  * GTG prefix AGGREGATION is cumulative (``gtg_prefix_mode='cumsum'``,
    the default): a permutation's prefix models come from one streamed
    weighted cumulative sum over its clients in walk order
    (ops/aggregate.block_prefix_cumsum via _CumsumPrefixWalker), so a
    length-L walk moves O(L*P) HBM bytes where the per-prefix masked
    reduction moved O(L*N*P/chunk) — the N-fold structural win at the
    north-star N=1000 (docs/PERFORMANCE.md § GTG at scale).
    ``gtg_prefix_mode='masked'`` keeps the mask-weighted path as the
    differential-testing oracle.
"""

from __future__ import annotations

import math
import os
import pickle
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from distributed_learning_simulator_tpu.algorithms.base import RoundContext
from distributed_learning_simulator_tpu.algorithms.fedavg import FedAvg
from distributed_learning_simulator_tpu.utils.errors import is_device_oom
from distributed_learning_simulator_tpu.ops.aggregate import (
    block_prefix_cumsum,
    prefix_means_from_cumsum,
    subset_masks_all,
    subset_weighted_mean,
)
from distributed_learning_simulator_tpu.telemetry.client_stats import (
    ClientStats,
    attribution_crosscheck,
)
from distributed_learning_simulator_tpu.telemetry.valuation import (
    cohort_crc,
)
from distributed_learning_simulator_tpu.utils.logging import get_logger

_EVAL_CHUNK = 16  # subset models evaluated per batched XLA call
_PREFIX_BLOCK = 16  # GTG permutation prefixes fetched per fused call

#: Mesh axis the subset evaluator partitions its MODEL-BATCH dimension
#: over (mesh-sharded GTG, ROADMAP item 5). Distinct from the round
#: program's "clients" axis: the round shards the client stack, the
#: evaluator shards the vmapped subset/permutation-group axis with the
#: stack REPLICATED — each device evaluates its own slice of the wave's
#: subset models with no cross-device reduction anywhere.
SUBSET_AXIS = "subsets"


def eval_mesh_devices(config) -> int | None:
    """How many devices the Shapley subset evaluators shard their batch
    axis over: ``config.mesh_devices`` when a single process owns the
    whole mesh, else None (the serial evaluator). Multihost stays
    unsharded — the GTG walk is data-dependent HOST control flow, and a
    multi-process walk would need every process to replay identical
    truncation/convergence decisions against collectively-fetched
    utilities; single-host mesh sharding is the supported capability."""
    d = getattr(config, "mesh_devices", None) or 1
    if d <= 1 or getattr(config, "multihost", False):
        return None
    if getattr(config, "execution_mode", "vmap").lower() == "threaded":
        # The threaded oracle ignores mesh_devices everywhere else; its
        # record writer also predates the v10 gtg sub-object routing.
        return None
    return int(d)


class SubsetMemo(dict):
    """Subset-utility memo with cross-round reuse accounting.

    A plain dict everywhere the walk machinery is concerned (it only does
    ``s in memo`` / ``memo[s]`` / ``memo[s] = v``), plus bookkeeping for
    the cross-round reuse feature (``config.gtg_cross_round_memo``,
    ROADMAP item 4b): entries present at construction are the SEED —
    utilities carried over from an earlier round with the same cohort —
    and :meth:`hit_rate` reports what fraction of the subsets this walk
    actually requested were served from the seed instead of evaluated.
    Reused utilities describe the *earlier* round's client params; the
    reuse premise (GTG-Shapley's between-round truncation) is that subset
    utilities drift slowly once the model converges — the regime where
    round truncation fires anyway. The hit rate (and, for audit walks,
    the recorded fidelity correlation) is the self-policing measurement
    of that premise.

    What a hit SAVES depends on the prefix mode: under ``masked`` the
    deduplication in :func:`eval_subsets` skips the seeded subsets'
    evaluator calls outright (realized device savings); under the
    default ``cumsum`` the prefix walker must stream every position to
    maintain its carries, so a seeded prefix is still computed inside
    the fused wave and only its memo write is skipped — the hit rate
    then measures utility REUSE/stability, not device work avoided
    (the same caveat the walker's own docstring makes for within-round
    hits).
    """

    def __init__(self, seed: dict | None = None):
        super().__init__(seed or {})
        self._seeded = frozenset(self)
        self._hits: set = set()
        self._inserted = 0

    def __contains__(self, key) -> bool:
        present = super().__contains__(key)
        if present and key in self._seeded:
            self._hits.add(key)
        return present

    def __setitem__(self, key, value) -> None:
        if not super().__contains__(key):
            self._inserted += 1
        super().__setitem__(key, value)

    @property
    def evaluated(self) -> int:
        """Subsets actually evaluated into this memo (seeded entries
        excluded) — the honest ``gtg_subset_evals`` cost unit; equals
        ``len(self)`` when unseeded."""
        return self._inserted

    def hit_rate(self) -> float | None:
        """Fraction of requested subsets served from the cross-round seed
        (None when the walk requested nothing)."""
        requested = len(self._hits) + self._inserted
        if requested == 0:
            return None
        return len(self._hits) / requested


def eval_subsets(evaluator, client_params, sizes, prev_global,
                 eval_batches, n: int, memo, subset_sets) -> None:
    """Evaluate the listed subsets (frozensets of client indices) into
    ``memo``, deduplicating against it — the ONE mask-building path shared
    by the masked walk mode, the grand/empty-coalition seeds, and the
    valuation auditor (telemetry/valuation.py)."""
    todo = list(dict.fromkeys(s for s in subset_sets if s not in memo))
    if not todo:
        return
    mask_rows = np.zeros((len(todo), n), dtype=np.float32)
    for r, s in enumerate(todo):
        mask_rows[r, list(s)] = 1.0
    vals = evaluator(
        client_params, sizes, mask_rows, prev_global, eval_batches
    )
    for s, v in zip(todo, vals):
        memo[s] = float(v)


def _gtg_converged(records: list[np.ndarray], n: int, last_k: int,
                   converge_criteria: float) -> bool:
    converge_min = max(30, n)  # GTG_shapley_value_server.py:15
    # last_k + 1 records minimum: with a configurable last_k above the
    # reference's 30-record floor, running_means[-last_k:] would silently
    # truncate and a mean flat over fewer samples than the user asked to
    # compare could fire convergence early.
    if len(records) <= max(converge_min, last_k):
        return False
    # Reference semantics (GTG_shapley_value_server.py:82-91): each of
    # the last_k running means is compared to the FINAL running mean —
    # relative error averaged over the worker axis — and sampling stops
    # when the largest of those k errors is within converge_criteria.
    # (NOT successive diffs: a running mean drifting steadily has small
    # per-step changes but large distance-to-final, and the reference
    # keeps sampling in that regime.) Note the last_k window INCLUDES
    # the final mean itself (its error is trivially 0, so last_k-1
    # comparisons are informative) — that is the reference's exact
    # slice, kept verbatim for parity.
    all_arr = np.stack(records)
    cumsum = np.cumsum(all_arr, axis=0)
    counts = np.arange(1, len(records) + 1)[:, None]
    running_means = (cumsum / counts)[-last_k:]
    final = running_means[-1:]
    errors = np.mean(
        np.abs(running_means - final) / (np.abs(final) + 1e-12), axis=1
    )
    return bool(np.max(errors) <= converge_criteria)


def gtg_walk(evaluator, client_params, sizes, prev_global, eval_batches,
             n: int, rng, *, eps: float, cap: int, last_k: int,
             converge_criteria: float, trunc_ref: float,
             prefix_mode: str = "cumsum", memo=None,
             starts_per_iteration: int | None = None):
    """One round's GTG permutation-sampling walk over an ``n``-client
    cohort: Monte-Carlo marginal records with eps-truncation, shared
    waves, and the cross-walk subset memo.

    Extracted from ``GTGShapley.post_round`` so the valuation auditor
    (telemetry/valuation.py) runs the EXACT same estimator on the current
    round's cohort — one walk implementation, no drift between the
    offline scorer and the in-line audit. Returns
    ``(sv_arr, n_perms, converged)``; utilities accumulate into ``memo``
    (a fresh dict when None — pass a :class:`SubsetMemo` seeded from an
    earlier round for cross-round reuse).

    ``starts_per_iteration`` truncates a sampling iteration to that many
    permutations (first elements drawn without replacement from ``rng``
    instead of "one per worker") — the audit walk's budget knob; None
    keeps the reference's one-permutation-per-worker iteration.
    """
    if memo is None:
        memo = {}
    eval_subsets(
        evaluator, client_params, sizes, prev_global, eval_batches, n,
        memo, [frozenset()],
    )  # u(empty): every walk's starting value
    walker = None
    if prefix_mode == "cumsum":
        walker = _CumsumPrefixWalker(
            evaluator, client_params, sizes, prev_global, eval_batches, n,
        )
    records: list[np.ndarray] = []
    n_perms = 0
    converged = False
    while not converged and n_perms < cap:
        # One permutation starting with each worker (:42-49) — or, for a
        # budgeted audit walk, with each of a sampled subset of workers.
        # The whole sampling iteration is evaluated in shared WAVES: wave
        # w requests prefix block [wB, wB+B) for EVERY still-active
        # permutation in one batched evaluator call (the memo dedups
        # shared prefixes), instead of walking the permutations one at a
        # time — at N=128 this cuts the sequential host dispatch+fetch
        # cycles per iteration from O(n * n/B) to n/B. The
        # per-permutation walk (eps-truncation semantics :51-61,
        # truncated step keeps v_prev so its marginal is exactly 0) is
        # unchanged, so within one sampling iteration the records — and
        # therefore SVs, permutation counts and the convergence point —
        # match a sequential walk over the same permutations. Two
        # bookkeeping differences vs walking one permutation at a time:
        # prefixes evaluated past a mid-iteration convergence are extra
        # (they land in the memo/metric pickle), and all shuffles are
        # drawn up front, so on mid-iteration convergence the RNG stream
        # position differs from a lazily-drawing walk (later rounds
        # sample different — equally valid — permutations).
        if starts_per_iteration is None or starts_per_iteration >= n:
            starts = list(range(n))
        else:
            starts = [
                int(s) for s in
                rng.choice(n, size=starts_per_iteration, replace=False)
            ]
        m = len(starts)
        perms = []
        for first in starts:
            rest = [i for i in range(n) if i != first]
            rng.shuffle(rest)
            perms.append([first] + rest)
        if walker is not None:
            walker.reset()  # fresh zero carries for this iteration
        marginals = np.zeros((m, n), dtype=np.float64)
        v_prev = [memo[frozenset()]] * m
        truncated = [False] * m
        for j0 in range(0, n, _PREFIX_BLOCK):
            j1 = min(j0 + _PREFIX_BLOCK, n)
            active: list[int] = []
            for p_idx in range(m):
                if truncated[p_idx] or (
                    abs(trunc_ref - v_prev[p_idx]) < eps
                ):
                    truncated[p_idx] = True
                else:
                    active.append(p_idx)
            if not active:
                break  # every permutation truncated
            if walker is not None:
                walker.eval_block(perms, active, j0, j1, memo)
            else:
                eval_subsets(
                    evaluator, client_params, sizes, prev_global,
                    eval_batches, n, memo,
                    [
                        frozenset(perms[p][: j + 1])
                        for p in active for j in range(j0, j1)
                    ],
                )
            for p_idx in active:
                perm = perms[p_idx]
                vp = v_prev[p_idx]
                for j in range(j0, j1):
                    if abs(trunc_ref - vp) >= eps:
                        v_j = memo[frozenset(perm[: j + 1])]
                    else:
                        v_j = vp  # truncated: marginal exactly 0
                    marginals[p_idx, perm[j]] = v_j - vp
                    vp = v_j
                v_prev[p_idx] = vp
        for p_idx in range(m):
            records.append(marginals[p_idx].copy())  # SURVEY 2.1#10
            n_perms += 1
            if _gtg_converged(records, n, last_k, converge_criteria):
                converged = True
                break
    return np.mean(np.stack(records), axis=0), n_perms, converged


def _sv_crosscheck_extra(ctx: RoundContext, sv_arr, config) -> dict:
    """Utility-attribution cross-check (telemetry/client_stats.py): when
    the round carried per-client stats, report the correlation between
    the expensive Shapley attribution and the cheap in-round signal
    (local loss improvement). Reads the matrix the host loop ALREADY
    fetched (ctx.extra, populated only on client_stats_every cadence
    rounds — no second device transfer, and off-cadence rounds don't
    grow a v3-era field in their un-upgraded record); falls back to the
    device array for direct post_round callers, cadence-gated the same
    way. Empty dict when stats are off, off-cadence, or the correlation
    is degenerate."""
    stats = ctx.extra.get("client_stats_np")
    if stats is None:
        stats_dev = ctx.aux.get("client_stats")
        cs = ClientStats.from_config(config)
        if (
            stats_dev is None
            or cs is None
            or not cs.fetch_round(ctx.round_idx)
        ):
            return {}
        stats = np.asarray(stats_dev)
    corr = attribution_crosscheck(sv_arr, stats)
    return {} if corr is None else {"sv_stats_corr": corr}


def _resolve_eval_dtype(config, default: str) -> str:
    """Per-algorithm ``shapley_eval_dtype='auto'`` resolution (ADVICE r5):
    exact multi-round Shapley reads the stack in f32 — it is the documented
    exact-parity path with no Monte-Carlo noise to hide bf16 rounding in —
    while GTG keeps bf16, where halving the dominant stack-read traffic is
    measured fidelity-free. An explicit config value wins for both."""
    dtype = getattr(config, "shapley_eval_dtype", "auto")
    return default if dtype == "auto" else dtype


def shapley_from_utilities(utilities: dict[frozenset, float], n: int) -> np.ndarray:
    """Exact Shapley values from a complete 2^n utility table.

    SV_i = sum over S not containing i of
    ``(u(S + {i}) - u(S)) / (n * C(n-1, |S|))`` — the marginal-contribution
    weighting of multiround_shapley_value_server.py:42-55.
    """
    sv = np.zeros(n, dtype=np.float64)
    ids = list(range(n))
    for size in range(n):
        weight = 1.0 / (n * math.comb(n - 1, size))
        from itertools import combinations

        for combo in combinations(ids, size):
            s = frozenset(combo)
            for i in ids:
                if i in s:
                    continue
                sv[i] += weight * (utilities[s | {i}] - utilities[s])
    return sv


def cap_eval_batches(eval_batches, max_samples: int | None):
    """First ``max_samples`` test samples as one padded batch (mask-exact).

    Subset-utility evaluations only — the round's reported metric always
    sees the full set. The flatten+slice happens once per round on device;
    the evaluator's jitted program then runs on the smaller static shape.
    """
    if max_samples is None:
        return eval_batches
    xb, yb, mb = eval_batches
    bs = xb.shape[1]
    total = xb.shape[0] * bs
    k = min(max_samples, total)
    flat = lambda a: a.reshape((total,) + a.shape[2:])  # noqa: E731
    if k < bs:
        # One smaller batch: strictly below the eval_batch_size activation
        # envelope, and masked-out samples cost no compute (the cap's whole
        # point — padding to bs would run the full batch masked).
        return (flat(xb)[:k][None], flat(yb)[:k][None], flat(mb)[:k][None])
    # k spans batches: keep the eval_batch_size scan granularity (the
    # subset evaluator vmaps _EVAL_CHUNK models over each batch, so one
    # giant [1, k] batch would blow the memory envelope bs exists to
    # bound); trim the remainder via the mask.
    n_batches = min((k + bs - 1) // bs, xb.shape[0])
    take = n_batches * bs
    reshape = lambda a: a[:take].reshape(  # noqa: E731
        (n_batches, bs) + a.shape[1:]
    )
    keep = jnp.asarray(np.arange(take) < k, mb.dtype)
    return (
        reshape(flat(xb)),
        reshape(flat(yb)),
        (flat(mb)[:take] * keep).reshape((n_batches, bs) + mb.shape[2:]),
    )


class _SubsetEvaluator:
    """Chunked, memoized evaluation of subset-model test metrics.

    ``chunk`` (config.shapley_eval_chunk) sets how many subset models one
    batched XLA call materializes+evaluates. Each call re-reads the full
    ``[n_clients, params]`` stack for its weighted means, so a larger
    chunk amortizes that read across more subsets — at N=1000 (1.8 GB
    stack) chunk 16 re-reads ~30 TB over a 266k-subset round; chunk 64
    cuts it 4x. The ceiling is activation memory: chunk models x
    eval-batch activations live at once.

    **Mesh sharding** (``mesh_devices > 1``, single host): the vmapped
    model-batch axis of each fused call is partitioned over a
    ``SUBSET_AXIS`` device mesh with the client stack, sizes, previous
    global and eval batches REPLICATED — one call then evaluates
    ``chunk x D`` subset models, ``chunk`` per device, in ~the serial
    call's wall time. Per-device call shapes are IDENTICAL to the
    serial evaluator's (the width scales with D exactly so each
    device's local program is the serial program), which is what makes
    sharded utilities — and therefore SVs, permutation counts, eval
    counts, and the memo contents — bit-identical to the serial walk
    (tests/test_gtg_mesh.py pins this at forced D=2). There are no
    cross-device reductions anywhere: a subset's weighted mean contracts
    over the REPLICATED client axis on whichever device owns that subset
    row, in the serial reduction order.
    """

    def __init__(self, eval_fn, chunk: int = _EVAL_CHUNK,
                 eval_dtype: str = "float32",
                 mesh_devices: int | None = None):
        self._chunk = int(chunk)
        self._eval_dtype = jnp.dtype(eval_dtype)
        self._mesh = None
        self._devices = 1
        if mesh_devices is not None and mesh_devices > 1:
            from distributed_learning_simulator_tpu.parallel.mesh import (
                make_mesh,
            )

            self._mesh = make_mesh(int(mesh_devices), axis_name=SUBSET_AXIS)
            self._devices = int(mesh_devices)
            self._rep = NamedSharding(self._mesh, PartitionSpec())
            self._shd = NamedSharding(
                self._mesh, PartitionSpec(SUBSET_AXIS)
            )
        # One-slot identity caches for the per-round replicated operands:
        # the walk calls the evaluator hundreds of times per round with
        # the SAME stack/sizes/prev/batches objects, and re-running the
        # placement tree_map per call would pay leaves x calls of no-op
        # device_puts.
        self._role_cache: dict[str, tuple] = {}

        # eval_fn(params, xb, yb, mb) -> {'loss','accuracy'}
        def eval_one(client_params, sizes, mask, prev_global, xb, yb, mb):
            params = subset_weighted_mean(client_params, sizes, mask, prev_global)
            return eval_fn(params, xb, yb, mb)["accuracy"]

        self._eval_chunk = jax.jit(
            jax.vmap(eval_one, in_axes=(None, None, 0, None, None, None, None))
        )

        # GTG cumsum path (gtg_prefix_mode='cumsum'): ONE fused XLA call per
        # group of G permutations advances their walks by a whole prefix
        # block — gather the block's clients, extend the carried running
        # sums (block_prefix_cumsum), materialize the G*B prefix models by a
        # cheap divide, and evaluate them — so each evaluated prefix reads
        # O(P) gathered bytes instead of the masked path's O(N*P/chunk)
        # stack re-read, and the C*N*P mask-contraction MACs per call
        # disappear outright. ``carry``/``carry_t`` hold exactly this
        # group's G running sums ([G, ...] leaves — the walker compacts the
        # wave's active rows host-side), so a call's carry traffic is
        # O(G*P), an eighth of the block models it evaluates; a
        # whole-cohort slot array with scatter updates was measured 6x
        # SLOWER than the masked path on backends without in-place buffer
        # donation (each call copied all N carries).
        def prefix_wave(client_params, sizes, carry, carry_t, perm_block,
                        prev_global, xb, yb, mb):
            cs_tree, totals = block_prefix_cumsum(
                client_params, sizes, perm_block, carry, carry_t,
            )
            new_carry = jax.tree_util.tree_map(
                lambda cs: cs[:, -1], cs_tree
            )
            params = prefix_means_from_cumsum(cs_tree, totals, prev_global)
            g, b = perm_block.shape
            flat = jax.tree_util.tree_map(
                lambda p: p.reshape((g * b,) + p.shape[2:]), params
            )
            accs = jax.vmap(
                lambda pp: eval_fn(pp, xb, yb, mb)["accuracy"]
            )(flat)
            return accs.reshape(g, b), new_carry, totals[:, -1]

        self._prefix_wave = jax.jit(prefix_wave)

    @property
    def eval_dtype(self):
        return self._eval_dtype

    @property
    def devices(self) -> int:
        """Devices the model-batch axis is partitioned over (1 = serial)."""
        return self._devices

    @property
    def call_width(self) -> int:
        """Nominal subset models per fused call: the configured chunk
        times the mesh width (each device keeps the serial chunk's
        activation envelope — and the serial call's exact shapes)."""
        return self._chunk * self._devices

    def _place_rep(self, role, tree):
        """Replicate a per-round operand over the subset mesh ONCE
        (identity-cached per role; serial mode passes through untouched).
        """
        if self._mesh is None:
            return tree
        cached = self._role_cache.get(role)
        if cached is not None and cached[0] is tree:
            return cached[1]
        placed = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, self._rep), tree
        )
        self._role_cache[role] = (tree, placed)
        return placed

    def _shard_rows(self, tree):
        """Partition a per-call tree's LEADING (model-batch) axis over
        the subset mesh; the serial path keeps today's jnp.asarray."""
        if self._mesh is None:
            return jax.tree_util.tree_map(jnp.asarray, tree)
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, self._shd), tree
        )

    def release_round(self):
        """Drop the per-round placement cache at the END of a walk. In
        mesh mode the cache holds BOTH the caller's stack and its D-way
        replicated copy; without this release those buffers would stay
        pinned through the NEXT round's training — an extra full-stack
        HBM footprint the serial evaluator never held. Every walk driver
        (GTG/multiround post_round, the valuation auditor) calls it when
        its round's evaluations are done; a serial evaluator's cache is
        never populated, so this is a no-op there."""
        self._role_cache.clear()

    def _reraise_oom(self, e, n_models: int, eval_batches,
                     min_chunk: int = 1):
        """Shared actionable-hint treatment for device OOMs in both the
        masked-chunk and cumsum prefix-wave paths: the envelope is
        ``n_models`` subset models x eval-batch activations resident at
        once (measured: the full-10k-sample set at chunk 64 exceeds one
        chip on cnn_tpu while chunk 16 fits — docs/PERFORMANCE.md § Scale
        validation). ``min_chunk`` is the path's floor on the call width:
        the cumsum prefix wave cannot go below one block of
        ``_PREFIX_BLOCK`` models, so suggesting a smaller chunk there
        would send the user into the identical crash."""
        xb = eval_batches[0]
        n_eval = int(xb.shape[0]) * int(xb.shape[1])
        suggestion = max(self._chunk // 4, min_chunk)
        chunk_advice = (
            f"Lower shapley_eval_chunk (e.g. {suggestion}) or cap "
            if suggestion < self._chunk
            # Mirrors _oom_hint's exceeded-even-at-minimum branch: when a
            # smaller chunk cannot shrink the call (chunk <= 4 on the
            # masked path, chunk <= one prefix block on the cumsum path),
            # the only lever left is the eval-sample cap.
            else f"shapley_eval_chunk={self._chunk} is already minimal — cap "
        )
        raise RuntimeError(
            "device OOM inside the Shapley subset evaluator: "
            f"{n_models} subset models x ~{n_eval} "
            "eval samples of activations were resident at once. "
            + chunk_advice +
            "shapley_eval_samples (subset utilities only; the "
            "round metric keeps the full test set)."
        ) from e

    def prepare_stack(self, client_params):
        """Cast the [n_clients, ...] stack to the evaluator read dtype ONCE
        per round (config.shapley_eval_dtype). Each batched call re-reads
        the whole stack for its subset weighted means — the dominant HBM
        traffic of a large-N GTG round — so a bf16 stack halves it; the
        tensordot still accumulates f32 (ops/aggregate.subset_weighted_mean)
        and the subset model handed to eval is f32-ranged."""
        if self._eval_dtype == jnp.float32:
            # Under a mesh, also re-place the (possibly client-axis-
            # sharded) stack REPLICATED over the subset mesh once per
            # round — one all-gather, amortized over every fused call.
            return self._place_rep("stack", client_params)
        cast = jax.tree_util.tree_map(
            lambda a: a.astype(self._eval_dtype), client_params
        )
        # Materialize now: the cast must happen once, not get re-fused into
        # every downstream evaluator call by lazy dispatch.
        return self._place_rep("stack", jax.block_until_ready(cast))

    def __call__(self, client_params, sizes, masks, prev_global, eval_batches):
        """masks: [M, n] numpy 0/1. Returns [M] numpy accuracies.

        All chunks are dispatched first and fetched with ONE device_get:
        per-chunk fetches each pay a full device->host round-trip and
        serialize dispatch with execution. Under a
        subset mesh each call carries ``chunk x D`` mask rows sharded over
        the devices (``chunk`` per device — the serial call's shapes), so
        the loop makes D-fold fewer dispatches over the same mask list in
        the same order; padded garbage rows are discarded host-side as
        before.
        """
        client_params = self._place_rep("stack", client_params)
        sizes = self._place_rep("sizes", sizes)
        prev_global = self._place_rep("prev_global", prev_global)
        xb, yb, mb = self._place_rep("batches", tuple(eval_batches))
        size = self.call_width
        pending = []
        try:
            for start in range(0, len(masks), size):
                chunk = masks[start : start + size]
                pad = size - len(chunk)
                if pad:
                    chunk = np.concatenate(
                        [chunk, np.zeros((pad, chunk.shape[1]), np.float32)]
                    )
                vals = self._eval_chunk(
                    client_params, sizes, self._shard_rows(chunk),
                    prev_global, xb, yb, mb,
                )
                pending.append(vals[: size - pad] if pad else vals)
            return np.concatenate(jax.device_get(pending))
        except jax.errors.JaxRuntimeError as e:
            if not is_device_oom(e):
                raise
            # Per-DEVICE width: the resident-activation envelope the hint
            # sizes against is each device's slice, not the call total.
            self._reraise_oom(e, self._chunk, eval_batches)


class _CumsumPrefixWalker:
    """Device-side state of one GTG sampling iteration's permutation walks
    under ``gtg_prefix_mode='cumsum'``.

    Per active permutation, a carry row holds the f32 running weighted sum
    (and total weight) of the walked prefix — compacted each wave to just
    the still-active walks; :meth:`eval_block` advances a wave of them by
    one prefix block, batching ``group`` permutations' block-cumsums per
    fused evaluator call (this replaces the masked path's ``_PREFIX_BLOCK``
    wave gather: same wave-major structure, same single fetch per wave, but
    each evaluated prefix costs O(P) gathered bytes instead of an
    O(N*P/chunk) share of a full stack re-read). Nothing is ever
    recomputed: the carry IS the sliceable cumsum, streamed block by block,
    and an eps-truncated walk simply never touches the blocks past its
    stopping point.

    Bookkeeping parity with the masked path: the same prefix sets land in
    the memo (memo-first on duplicates, so a set evaluated twice — e.g. the
    grand coalition, reached by every full-length walk — keeps one
    deterministic value), so ``metric_<round>.pkl`` and the walk's
    truncation/marginal decisions see identical keys. Device-side work may
    exceed the masked path's on memo HITS (a hit still computes inside the
    fused call and is discarded host-side); at large N a walk re-visits
    almost no sets, so the waste is a handful of inferences per iteration.
    """

    def __init__(self, evaluator, client_params, sizes, prev_global,
                 eval_batches, n: int):
        self._ev = evaluator
        # Per-round operands replicated over the subset mesh once (no-op
        # pass-through for the serial evaluator).
        self._stack = evaluator._place_rep("stack", client_params)
        self._sizes = evaluator._place_rep("sizes", sizes)
        self._prev_global = evaluator._place_rep("prev_global", prev_global)
        self._eval_batches = evaluator._place_rep(
            "batches", tuple(eval_batches)
        )
        self._n = n
        self._block = min(_PREFIX_BLOCK, n)
        # Group size: the fused call evaluates group x block prefix models,
        # so group*block matches the masked path's shapley_eval_chunk
        # activation envelope (floor one group — cumsum mode's minimum call
        # width is one block of models). Under a subset mesh the group
        # scales by the device count: each device then advances the
        # SERIAL group's worth of permutations — per-device call shapes
        # identical to the serial walker's, which is the bit-identity
        # mechanism (class docstring of _SubsetEvaluator).
        self._group = (
            max(1, evaluator._chunk // self._block) * evaluator.devices
        )
        self._carry = None
        self._carry_t = None
        self._row_of: dict[int, int] = {}

    def reset(self):
        """Drop the carries for a fresh sampling iteration (every walk
        restarts at the empty prefix — materialized lazily as zero rows on
        the first wave)."""
        self._carry = None
        self._carry_t = None
        self._row_of = {}

    def _wave_carries(self, active):
        """Compact the carry rows of this wave's active permutations into
        one contiguous [ceil(A/G)*G, ...] tree (row k = active[k]; the tail
        pads by repeating a row so every group slice is exactly [G, ...] —
        one traced shape, garbage results discarded host-side). ONE gather
        per wave: truncated permutations' rows are dropped here, which is
        all the 'slicing' an eps-truncated walk ever needs — its cumsum
        simply stops being carried, nothing is recomputed."""
        g_size = self._group
        padded = -(-len(active) // g_size) * g_size
        if self._carry is None:  # first wave: every carry is the empty sum
            carry = jax.tree_util.tree_map(
                lambda x: jnp.zeros((padded,) + x.shape[1:], jnp.float32),
                self._stack,
            )
            return carry, jnp.zeros((padded,), jnp.float32)
        rows = np.asarray(
            [self._row_of[p] for p in active], dtype=np.int32
        )
        rows = np.concatenate(
            [rows, np.full((padded - len(rows),), rows[-1], np.int32)]
        )
        return (
            jax.tree_util.tree_map(lambda c: c[rows], self._carry),
            self._carry_t[rows],
        )

    def eval_block(self, perms, active, j0: int, j1: int, memo) -> None:
        """Advance every permutation in ``active`` through prefix positions
        [j0, j1), filling ``memo`` with the block's utilities. All groups
        are dispatched first and fetched with ONE device_get (the same
        single-fetch discipline as the masked evaluator)."""
        g_size, b_size = self._group, self._block
        carry, carry_t = self._wave_carries(active)
        pending = []
        new_carries = []
        try:
            for start in range(0, len(active), g_size):
                group = active[start : start + g_size]
                # A short final block (j1 - j0 < block) pads its trailing
                # positions with client 0 — that corrupts the carry past
                # position n-1, which no later block exists to read.
                block = np.zeros((g_size, b_size), np.int32)
                for g, p in enumerate(group):
                    block[g, : j1 - j0] = perms[p][j0:j1]
                # Per-call carries/indices partition over the subset mesh
                # (group-axis rows; serial mode = today's jnp.asarray /
                # pass-through): a short final group was already padded
                # by _wave_carries, so the group axis always splits
                # evenly over the devices.
                c_g = self._ev._shard_rows(jax.tree_util.tree_map(
                    lambda c: c[start : start + g_size], carry
                ))
                accs, nc, nct = self._ev._prefix_wave(
                    self._stack, self._sizes, c_g,
                    self._ev._shard_rows(carry_t[start : start + g_size]),
                    self._ev._shard_rows(block), self._prev_global,
                    *self._eval_batches,
                )
                pending.append((group, accs))
                new_carries.append((nc, nct))
            fetched = jax.device_get([a for _, a in pending])
        except jax.errors.JaxRuntimeError as e:
            if not is_device_oom(e):
                raise
            self._ev._reraise_oom(
                # Per-DEVICE width: each device holds its group slice's
                # models; g_size is a multiple of the device count.
                e, (g_size // self._ev.devices) * b_size,
                self._eval_batches, min_chunk=b_size,
            )
        if len(new_carries) == 1:
            self._carry, self._carry_t = new_carries[0]
        else:
            self._carry = jax.tree_util.tree_map(
                lambda *xs: jnp.concatenate(xs),
                *[nc for nc, _ in new_carries],
            )
            self._carry_t = jnp.concatenate([t for _, t in new_carries])
        self._row_of = {p: k for k, p in enumerate(active)}
        for (group, _), acc in zip(pending, fetched):
            for g, p in enumerate(group):
                perm = perms[p]
                for b in range(j1 - j0):
                    s = frozenset(perm[: j0 + b + 1])
                    if s not in memo:
                        memo[s] = float(acc[g, b])


def _check_shapley_config(config) -> None:
    """Shared preconditions for both Shapley servers.

    Subset utilities are plain weighted means of client params, so every
    client must participate and no server optimizer may reshape the global
    model (else the grand coalition's utility disagrees with the round
    metric and the Shapley values are silently wrong).
    """
    if getattr(config, "participation_fraction", 1.0) < 1.0:
        raise ValueError(
            "Shapley scoring needs every client's update each round; "
            "participation_fraction < 1 is not supported"
        )
    server_opt = getattr(config, "server_optimizer_name", "none") or "none"
    if server_opt.lower() not in ("none", ""):
        raise ValueError(
            "Shapley scoring assumes plain FedAvg aggregation; set "
            "server_optimizer_name='none'"
        )
    if getattr(config, "aggregation", "mean").lower() != "mean":
        raise ValueError(
            "Shapley scoring assumes the weighted-mean aggregator (subset "
            "utilities are weighted means); set aggregation='mean'"
        )
    from distributed_learning_simulator_tpu.robustness.faults import (
        FailureModel,
    )

    if FailureModel.from_config(config) is not None:
        # The subset-utility memo keys subsets of a FIXED cohort whose
        # every update is honest; a client that drops out or uploads
        # garbage silently invalidates every memoized utility that
        # includes it — refuse rather than score garbage.
        raise ValueError(
            "Shapley scoring refuses failure injection: the subset-utility "
            "memo assumes a fixed cohort of honest updates; set "
            "failure_mode='none'"
        )
    if getattr(config, "async_mode", "off").lower() == "on":
        # Same fixed-cohort assumption against the time axis: a late
        # upload applied rounds later (robustness/arrivals.py) has no
        # place in a subset utility evaluated against THIS round's
        # metric — refuse rather than attribute stale updates.
        raise ValueError(
            "Shapley scoring refuses async_mode='on': subset utilities "
            "assume a synchronous fixed cohort; set async_mode='off'"
        )


class MultiRoundShapley(FedAvg):
    """Exact multi-round Shapley: full-powerset utility per round.

    Parity with servers/multiround_shapley_value_server.py. 2^N subsets per
    round — exact only for small N (the reference's canonical run is N=4,
    simulator.sh:1); refuse N > 16.
    """

    name = "multiround_shapley_value"
    keep_client_params = True
    supports_round_pipelining = False  # post_round consumes round metrics
    # post_round takes every subset's mean around ctx.prev_global_params.
    supports_global_donation = False
    # Streamed residency (config.client_residency='streamed'): subset
    # re-evaluation consumes the RESIDENT aux['client_params'] stack —
    # overrides the FedAvg-family opt-in; the simulator refuses with
    # the cause.
    supports_streamed_residency = False
    # Mesh capability (ROADMAP item 5): post_round's subset evaluation
    # partitions its vmapped mask-batch axis over a single-host mesh
    # (mesh_devices > 1) with the client stack replicated — subset
    # utilities are independent, so sharding is pure throughput.
    # Multihost keeps the serial evaluator (eval_mesh_devices).
    shards_subset_eval = True

    def __init__(self, config):
        super().__init__(config)
        _check_shapley_config(config)
        if config.worker_number > 16:
            # The ACTUAL client count may be smaller than worker_number
            # (caller-supplied ClientData, ADVICE r4), so the constructor
            # only warns; the hard 2^N refusal fires in check_cohort —
            # still before any training, from make_round_fn (vmap path)
            # and the threaded runner's pre-spawn check.
            get_logger().warning(
                "exact Shapley needs 2^N subset evaluations and "
                "worker_number=%d > 16; this run will be refused at build "
                "time unless the injected client data has <= 16 clients",
                config.worker_number,
            )
        self.shapley_values: dict[int, dict[int, float]] = {}
        self._evaluator = None

    def check_cohort(self, n_clients: int) -> None:
        if n_clients > 16:
            raise ValueError(
                "exact Shapley needs 2^N subset evaluations; "
                f"N={n_clients} > 16. "
                "Use GTG_shapley_value for large client counts."
            )

    def prepare(self, apply_fn, eval_fn):
        self._evaluator = _SubsetEvaluator(
            eval_fn,
            chunk=getattr(self.config, "shapley_eval_chunk", _EVAL_CHUNK),
            eval_dtype=_resolve_eval_dtype(self.config, default="float32"),
            mesh_devices=eval_mesh_devices(self.config),
        )

    def post_round(self, ctx: RoundContext) -> dict:
        n = int(ctx.sizes.shape[0])
        if n > 16:
            # Backstop for non-worker_number client counts (heterogeneous
            # client_data overrides); normally caught in __init__.
            raise ValueError(
                f"exact Shapley needs 2^N subset evaluations; N={n} > 16. "
                "Use GTG_shapley_value for large client counts."
            )
        logger = get_logger()
        round_idx = ctx.round_idx
        threshold = getattr(self.config, "round_trunc_threshold", None)
        metric_now = float(ctx.metrics["accuracy"])
        metric_prev = (
            float(ctx.prev_metrics["accuracy"]) if ctx.prev_metrics else None
        )
        # Round truncation (multiround_shapley_value_server.py:17-32), with
        # the threshold actually plumbed (fixes SURVEY 2.1#9).
        if (
            threshold is not None
            and metric_prev is not None
            and abs(metric_now - metric_prev) <= threshold
        ):
            sv = {i: 0.0 for i in range(n)}
            self.shapley_values[round_idx] = sv
            logger.info("round %d: truncated, shapley values all 0", round_idx)
            return {"shapley_values": sv}

        masks = subset_masks_all(n, include_empty=True)
        utilities_arr = self._evaluator(
            self._evaluator.prepare_stack(ctx.aux["client_params"]),
            ctx.sizes, masks,
            ctx.prev_global_params,
            cap_eval_batches(
                ctx.eval_batches,
                getattr(self.config, "shapley_eval_samples", None),
            ),
        )
        self._evaluator.release_round()
        utilities = {
            frozenset(np.flatnonzero(m).tolist()): float(u)
            for m, u in zip(masks, utilities_arr)
        }
        sv_arr = shapley_from_utilities(utilities, n)
        sv = {i: float(v) for i, v in enumerate(sv_arr)}
        self.shapley_values[round_idx] = sv
        # Artifact parity: pickle per-round subset metrics
        # (multiround_shapley_value_server.py:56-57 writes ./metric_<round>).
        if ctx.log_dir:
            path = os.path.join(ctx.log_dir, f"metric_{round_idx}.pkl")
            with open(path, "wb") as f:
                pickle.dump({tuple(sorted(k)): v for k, v in utilities.items()}, f)
        logger.info("round %d shapley values: %s", round_idx, sv)
        return {
            "shapley_values": sv,
            **_sv_crosscheck_extra(ctx, sv_arr, self.config),
        }


class GTGShapley(FedAvg):
    """GTG-Shapley: Monte-Carlo permutation sampling with guided truncation.

    Parity with servers/GTG_shapley_value_server.py (hyperparameter defaults
    at :11-18): per sampling iteration, one permutation starting with each
    worker (:42-49); within a permutation, prefix utilities are only
    "refreshed" while the running value is at least ``eps`` away from the
    full-aggregation metric (:51-61), with subset metrics memoized across the
    round; convergence when each of the last ``last_k`` running-mean SV
    estimates sits within ``converge_criteria`` relative distance of the
    current estimate (:79-100).
    """

    name = "GTG_shapley_value"
    keep_client_params = True
    supports_round_pipelining = False  # post_round consumes round metrics
    supports_global_donation = False  # the walk reads ctx.prev_global_params
    # Same as MultiRoundShapley: the permutation walk's subset utilities
    # assume a resident per-client stack; streamed residency is refused.
    supports_streamed_residency = False
    # Mesh capability (ROADMAP item 5): permutation walks are
    # independent given the memo, so the walk's prefix waves shard
    # their group axis over a single-host mesh — bit-identical to the
    # serial walk (per-device call shapes are the serial call's; see
    # _SubsetEvaluator). Sharded rounds record the schema-v10 ``gtg``
    # sub-object (devices, evals_per_s, wave width, walk seconds).
    shards_subset_eval = True

    def __init__(self, config):
        super().__init__(config)
        _check_shapley_config(config)
        self.shapley_values: dict[int, dict[int, float]] = {}
        self._evaluator = None
        self.eps = getattr(config, "gtg_eps", 1e-3)
        self.round_trunc_threshold = getattr(config, "round_trunc_threshold", None)
        if self.round_trunc_threshold is None:
            self.round_trunc_threshold = 0.01  # GTG default (:14)
        self.last_k = getattr(config, "gtg_last_k", 10)
        self.converge_criteria = getattr(config, "gtg_converge_criteria", 0.05)
        # None = auto max(500, 2N) at the actual client count (resolved in
        # _effective_cap): one sampling iteration draws N permutations and
        # convergence needs > max(30, N) records, so a cap below 2N can
        # never produce a converged estimate — it silently degrades to a
        # one-iteration Monte-Carlo run (VERDICT r4 weak #2).
        self.max_permutations = getattr(config, "gtg_max_permutations", None)
        # Cross-round subset-utility reuse (config.gtg_cross_round_memo):
        # {cohort crc32 -> the last walk's utility dict}; the latest
        # round's values replace older ones (freshest params win).
        self._memo_store: dict[int, dict] = {}
        self.gtg_memo_hit_rate: float | None = None
        if (
            self.max_permutations is not None
            and self.max_permutations < config.worker_number
        ):
            get_logger().warning(
                "gtg_max_permutations=%d < worker_number=%d: one sampling "
                "iteration draws one permutation per client, so the cap "
                "would be exceeded before it is ever checked; this run "
                "will be refused at build time unless the actual client "
                "count is <= the cap",
                self.max_permutations, config.worker_number,
            )
        self._rng = np.random.default_rng(getattr(config, "seed", 0) + 17)

    def check_cohort(self, n_clients: int) -> None:
        if self.max_permutations is None:
            return
        # Convergence needs MORE than max(30, N, last_k) marginal records
        # (one per permutation, _converged's gate), and one sampling
        # iteration draws N permutations.
        converge_floor = max(30, n_clients, self.last_k)
        if self.max_permutations < n_clients:
            raise ValueError(
                f"gtg_max_permutations={self.max_permutations} < "
                f"N={n_clients}: one GTG sampling iteration draws N "
                "permutations (one starting with each worker), so this "
                "cap cannot be honored — raise it to >= "
                f"{n_clients} (> {converge_floor} for a convergence-"
                "capable run) or leave it unset for auto max(500, 2N)"
            )
        if self.max_permutations <= converge_floor and not getattr(
            self, "_warned_mc_budget", False
        ):
            # Honorable but convergence can never fire: an explicit
            # small budget is a legitimate fixed-cost Monte-Carlo run —
            # allow it, but say what it is. (check_cohort runs from both
            # the simulator and make_round_fn — warn once.)
            self._warned_mc_budget = True
            get_logger().warning(
                "gtg_max_permutations=%d <= max(30, N=%d, last_k=%d): the "
                "convergence test needs more records than that, so every "
                "round will report a fixed-budget Monte-Carlo estimate "
                "with converged=False",
                self.max_permutations, n_clients, self.last_k,
            )

    def _effective_cap(self, n_clients: int) -> int:
        if self.max_permutations is not None:
            return self.max_permutations
        return max(500, 2 * n_clients)

    def prepare(self, apply_fn, eval_fn):
        self._evaluator = _SubsetEvaluator(
            eval_fn,
            chunk=getattr(self.config, "shapley_eval_chunk", _EVAL_CHUNK),
            eval_dtype=_resolve_eval_dtype(self.config, default="bfloat16"),
            mesh_devices=eval_mesh_devices(self.config),
        )

    def _converged(self, records: list[np.ndarray], n: int) -> bool:
        # Thin delegate: the convergence rule lives in _gtg_converged so
        # gtg_walk (and the valuation auditor riding it) shares it.
        return _gtg_converged(records, n, self.last_k, self.converge_criteria)

    def post_round(self, ctx: RoundContext) -> dict:
        n = int(ctx.sizes.shape[0])
        logger = get_logger()
        round_idx = ctx.round_idx
        metric_now = float(ctx.metrics["accuracy"])
        metric_prev = (
            float(ctx.prev_metrics["accuracy"]) if ctx.prev_metrics else None
        )
        if (
            metric_prev is not None
            and abs(metric_now - metric_prev) <= self.round_trunc_threshold
        ):
            sv = {i: 0.0 for i in range(n)}
            self.shapley_values[round_idx] = sv
            logger.info("round %d: truncated, shapley values all 0", round_idx)
            return {"shapley_values": sv, "gtg_permutations": 0}

        t_walk = time.perf_counter()
        client_params = self._evaluator.prepare_stack(ctx.aux["client_params"])
        # Cross-round memo (config.gtg_cross_round_memo, ROADMAP item 4b):
        # seed this round's subset-utility memo from the last round with
        # the SAME cohort (GTG requires full participation, so the cohort
        # — and its hash — is constant across rounds). Off (the default)
        # keeps the exact pre-feature per-round memo. Reused utilities
        # describe the earlier round's params (SubsetMemo docstring);
        # the recorded hit rate measures how much was reused.
        cohort_key = cohort_crc(None, n)
        cross_round = bool(
            getattr(self.config, "gtg_cross_round_memo", False)
        )
        seed = self._memo_store.get(cohort_key) if cross_round else None
        if seed:
            # The empty and grand coalitions anchor the walk (every
            # v_prev chain and the eps-truncation reference) — always
            # re-evaluate them against THIS round's params; only interior
            # subsets are reuse candidates.
            seed = {
                k: v for k, v in seed.items() if 0 < len(k) < n
            }
        memo = SubsetMemo(seed)
        eval_batches = cap_eval_batches(
            ctx.eval_batches,
            getattr(self.config, "shapley_eval_samples", None),
        )

        def utilities_for(masks_sets: list[frozenset]) -> None:
            eval_subsets(
                self._evaluator, client_params, ctx.sizes,
                ctx.prev_global_params, eval_batches, n, memo, masks_sets,
            )

        utilities_for([frozenset()])  # u(empty) = prev-global metric
        # eps-truncation reference: "running value close to the full-
        # aggregation metric" (:51-61). With shapley_eval_samples the
        # subset utilities come from a SUBSAMPLED estimator whose grand-
        # coalition value differs from the full-set round metric by
        # subsample noise >> eps — comparing across estimators would make
        # truncation fire never (or spuriously). The same cross-estimator
        # mismatch exists when the evaluator reads a non-f32 stack (ADVICE
        # r5): the bf16 estimator's grand-coalition utility sits bf16
        # rounding (~1e-3, the scale of eps itself) away from the f32
        # round metric. In either case use the grand-coalition utility
        # from the SAME estimator as the walked prefixes.
        if (
            getattr(self.config, "shapley_eval_samples", None) is not None
            or self._evaluator.eval_dtype != jnp.float32
        ):
            grand = frozenset(range(n))
            utilities_for([grand])
            trunc_ref = memo[grand]
        else:
            trunc_ref = metric_now
        cap = self._effective_cap(n)
        if cap < n:
            # Reachable only when post_round is driven without the build-
            # time check_cohort (direct API use); same semantics problem,
            # surfaced loudly instead of silently overrunning the cap.
            logger.warning(
                "gtg_max_permutations=%d < N=%d: the first sampling "
                "iteration alone draws N permutations; the cap will be "
                "exceeded and convergence cannot fire", cap, n,
            )
        # The walk itself — permutation sampling, shared waves,
        # eps-truncation, convergence — is module-level ``gtg_walk``
        # (shared verbatim with the valuation auditor,
        # telemetry/valuation.py). Prefix-aggregation mode
        # (config.gtg_prefix_mode): 'cumsum' (the default) streams each
        # permutation's weighted running sum block by block; 'masked' is
        # the per-prefix mask-weighted oracle
        # (tests/test_shapley.py::test_gtg_prefix_mode_equivalence). Both
        # modes share the RNG stream, the wave structure, the memo, and
        # the truncation/marginal bookkeeping, so a fixed seed yields the
        # same permutations and — utilities agreeing — identical records.
        sv_arr, n_perms, converged = gtg_walk(
            self._evaluator, client_params, ctx.sizes,
            ctx.prev_global_params, eval_batches, n, self._rng,
            eps=self.eps, cap=cap, last_k=self.last_k,
            converge_criteria=self.converge_criteria, trunc_ref=trunc_ref,
            prefix_mode=getattr(self.config, "gtg_prefix_mode", "cumsum"),
            memo=memo,
        )
        walk_seconds = time.perf_counter() - t_walk
        self._evaluator.release_round()
        sv = {i: float(v) for i, v in enumerate(sv_arr)}
        self.shapley_values[round_idx] = sv
        memo_extra = {}
        if self._evaluator.devices > 1:
            # Mesh-sharded walk provenance: the schema-v10 ``gtg``
            # sub-object (the simulator routes it through the shared
            # record builder). Attached ONLY when the walk actually
            # sharded, so serial GTG runs keep their pre-feature records
            # byte-identical — the established off-gate discipline.
            memo_extra["gtg"] = {
                "devices": self._evaluator.devices,
                "evals_per_s": (
                    round(memo.evaluated / walk_seconds, 1)
                    if walk_seconds > 0 and memo.evaluated else None
                ),
                # Walk parallelism: subset models per fused evaluator
                # call, partitioned over the devices (the serial chunk's
                # envelope per device).
                "wave_width": self._evaluator.call_width,
                "walk_seconds": round(walk_seconds, 3),
            }
        if cross_round:
            self._memo_store[cohort_key] = dict(memo)
            self.gtg_memo_hit_rate = memo.hit_rate()
            if self.gtg_memo_hit_rate is not None:
                # ROADMAP item 4b's tracked number: what fraction of this
                # walk's subset utilities earlier rounds already paid for.
                memo_extra["gtg_memo_hit_rate"] = round(
                    self.gtg_memo_hit_rate, 4
                )
        if ctx.log_dir:
            path = os.path.join(ctx.log_dir, f"metric_{round_idx}.pkl")
            with open(path, "wb") as f:
                pickle.dump(
                    {tuple(sorted(k)): v for k, v in memo.items()}, f
                )
        logger.info(
            "round %d shapley values (GTG, %d permutations, %d subset evals, "
            "converged=%s): %s",
            round_idx, n_perms, memo.evaluated, converged, sv,
        )
        return {
            "shapley_values": sv,
            "gtg_permutations": n_perms,
            # Evaluations THIS round paid for: cross-round memo hits are
            # excluded (they are the saving, not the cost); equals the
            # memo size exactly when gtg_cross_round_memo is off.
            "gtg_subset_evals": memo.evaluated,
            # Tracked by bench.py's gtg leg / scripts/measure_gtg_scale.py:
            # a converged round is the honest cost unit (a fixed-budget
            # Monte-Carlo round is cheaper but a different estimator).
            "gtg_converged": converged,
            **memo_extra,
            **_sv_crosscheck_extra(ctx, sv_arr, self.config),
        }
