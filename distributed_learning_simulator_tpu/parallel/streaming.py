"""Double-buffered host->HBM cohort pipeline (client_residency='streamed').

The resident round program keeps every per-client array device-resident
for the whole run, so HBM sizes by the POPULATION even when
``participation_fraction`` samples a tiny cohort. Under streamed
residency the full-N arrays live in a host shard store
(data/residency.py) and this module owns the transfer pipeline:

  * **cohort replay** — the round program's cohort draw is re-derived
    HOST-side from the round-key chain (``Algorithm.cohort_indices``,
    the PR 2/PR 6 round-key discipline), so the streamer knows WHICH
    clients a dispatch trains before it runs — no device round-trip;
  * **upload** — the cohort's data slices are gathered from the store
    and ``jax.device_put`` as the round program's pre-gathered operands
    (the streamed calling convention, algorithms/base.py);
  * **prefetch** — the NEXT dispatch's upload runs on a worker thread
    while the current dispatch computes, so at steady state the
    transfer cost is hidden behind compute (``overlap_ratio`` measures
    exactly how much: hidden transfer seconds / total transfer
    seconds);
  * **writeback** — persistent per-client state returned by the round
    scatters back into the host store, which is the source of truth
    between dispatches (checkpoints read it).

Every transfer is timed and byte-counted; the per-dispatch stats become
the schema-v5 ``stream`` sub-object of the metrics record
(utils/reporting.py) and the run totals feed the result dict's
``stream_overlap_ratio`` (bench.py's ``stream`` leg gates it through
scripts/compare_bench.py --stream-overlap-threshold). The cohort-draw
replay is timed too (the ``sample`` phase + the stream record's
``sampler``/``sample_ms`` fields): at N=1e6 the exact replay is the
~1 s host cost that used to hide inside ``client_step``
(``participation_sampler='hashed'`` removes it — ops/sampling.py).

**Mesh composition** (``mesh_devices > 1`` + streamed, single host):
the streamer uploads each cohort slice directly into the client-axis
``PartitionSpec`` layout — one ``jax.device_put`` per array against a
``NamedSharding`` whose client axis is the slice's cohort axis (axis
0), so the host->device transfer is split per shard by the
mesh's client-axis ownership and the round program consumes the slice
without a resharding copy. Double buffering is unchanged (the worker
thread's device_put targets the sharded layout directly) and the
writeback ``device_get`` gathers shard-local cohort state back to the
host store.
"""

from __future__ import annotations

import contextlib
import os
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

from distributed_learning_simulator_tpu.data.residency import (
    HostShardStore,
    plan_owner_assembly,
    tree_bytes,
)
from distributed_learning_simulator_tpu.telemetry import clock
from distributed_learning_simulator_tpu.utils.logging import get_logger

# Straggler injection for the distributed-tracing tests (chaos-harness
# precedent, robustness/chaos.py): when set, this host sleeps that many
# seconds before each spill-exchange barrier — the OTHER hosts' measured
# allgather wait then attributes the stall to this host. Inert unless
# the environment variable is set; never set it in production.
ENV_STRAGGLE = "DLS_STRAGGLE_S"


def _maybe_straggle() -> None:
    s = os.environ.get(ENV_STRAGGLE)
    if s:
        time.sleep(float(s))


@contextlib.contextmanager
def _maybe_span(rec, name: str, cat: str, **kw):
    """Span context when a recorder is attached, no-op otherwise —
    keeps the off-gate path free of even a null context object chain."""
    if rec is None:
        yield None
    else:
        with rec.span(name, cat, **kw) as extra:
            yield extra


def _nbytes(arrays) -> int:
    return sum(
        int(np.asarray(a).nbytes) for a in arrays if a is not None
    )


class CohortStreamer:
    """Owns the host shard store's device side: upload, prefetch, writeback.

    One dispatch's upload is a tuple ``(x, y, m, sizes, idx)`` of device
    arrays, cohort-shaped (``[cohort, ...]``). ``prefetch`` schedules the
    upload on the ONE worker thread (uploads are sequential by construction —
    double buffering needs exactly one in flight); ``acquire`` collects
    it, falling back to a synchronous upload when nothing (or the wrong
    cohort — e.g. after a preemption break) is pending.
    """

    def __init__(self, store: HostShardStore, algorithm, n_clients: int,
                 device=None, mesh=None):
        self.store = store
        self._algorithm = algorithm
        self._n = n_clients
        # device=None (the simulator's single-device runs) uploads
        # UNCOMMITTED to the backend's default device — matching the
        # resident program's jnp.asarray placement. Committedness is part
        # of the executable cache key: a committed round-0 upload turns
        # the round outputs committed, so round 1's params arrive with a
        # different sharding signature than round 0's and the round
        # program compiles twice (one spurious post-warmup compile).
        self._device = device
        # mesh (single-host client-axis mesh, parallel/mesh.py): uploads
        # device_put against a NamedSharding whose client axis is the
        # slice's cohort axis — the per-shard transfer addressed by the
        # mesh's client-axis ownership. Mutually exclusive with device.
        self._mesh = mesh
        # Per-round cohort-replay timing (ops/sampling.py modes): the
        # pending seconds drain into the next acquire's stats as
        # ``sample_ms``; ``last_sample_seconds`` lets the host loop carve
        # the draw out of the enclosing phase window (telemetry/phases).
        self._sampler = getattr(
            algorithm.config, "participation_sampler", "exact"
        ).lower()
        self._sample_pending = 0.0
        self.last_sample_seconds = 0.0
        # Cohort replay runs on the CPU backend when one exists: jax PRNG
        # draws are backend-deterministic, and tiny eager choice/split ops
        # must not interleave with the accelerator's round program. Must
        # be a LOCAL device: under multihost, jax.devices("cpu")[0] is
        # process 0's device globally, and committing the replay operand
        # to a remote device would turn the tiny replay jit into a
        # cross-process computation (observed as a deadlock on the
        # 2-process CPU harness).
        try:
            self._cpu = jax.local_devices(backend="cpu")[0]
        except (RuntimeError, IndexError):
            self._cpu = None
        get_logger().info(
            "cohort replay runs on %s",
            self._cpu if self._cpu is not None
            else f"the default device ({jax.local_devices()[0]}): "
                 "no local cpu backend",
        )
        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="cohort-upload"
        )
        self._pending = None  # (idx_list, future) of the prefetched upload
        # Distributed tracing (telemetry/spans.py): the simulator
        # attaches a recorder when span_trace='on' (plus this host's
        # clock offset vs host 0 and the current round index for skew
        # attribution); None keeps every path below span-free.
        self.span_recorder = None
        self.clock_offset_s = 0.0
        self.span_round: int | None = None
        # Run totals (the result dict's stream_* fields).
        self.totals = {
            "h2d_bytes": 0, "h2d_seconds": 0.0, "hidden_seconds": 0.0,
            "d2h_bytes": 0, "d2h_seconds": 0.0, "sample_seconds": 0.0,
        }

    def _placed(self, a):
        """device_put one upload array: uncommitted default device
        (single-device runs), the explicit device, or — under a mesh —
        the client-axis NamedSharding (the cohort axis leads)."""
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            spec = PartitionSpec(self._mesh.axis_names[0])
            return jax.device_put(a, NamedSharding(self._mesh, spec))
        if self._device is not None:
            return jax.device_put(a, self._device)
        return jax.device_put(a)

    # ---- cohort replay -----------------------------------------------------
    def cohort_for(self, round_key, n=None, alive=None, k=None):
        """Host replay of the cohort the round program draws from
        ``round_key`` (Algorithm.cohort_indices contract): a host numpy
        index array, or None when the cohort is the whole population.
        Timed: the draw cost (the exact replay's O(N log N) permutation
        vs the hashed mode's O(cohort) hash — ops/sampling.py) lands in
        the next acquire's ``sample_ms`` and the ``sample`` phase.

        ``n``/``alive``/``k`` serve ``population='dynamic'``
        (robustness/population.py): the draw covers the CURRENT
        registered index space with departed indices masked out, at the
        pinned startup cohort size — defaults keep the static replay
        byte-for-byte."""
        t0 = clock.monotonic()
        if self._cpu is not None:
            round_key = jax.device_put(round_key, self._cpu)
        idx = self._algorithm.cohort_indices(
            round_key, self._n if n is None else n,
            alive=alive, n_participants=k,
        )
        dt = clock.monotonic() - t0
        self._sample_pending += dt
        self.last_sample_seconds = dt
        self.totals["sample_seconds"] += dt
        return None if idx is None else np.asarray(idx)

    # ---- upload / prefetch -------------------------------------------------
    def _upload(self, idx_list):
        """Worker-thread body: gather + device_put + block, timed.

        ``idx_list`` holds the dispatch's one index array; the upload
        is cohort-shaped.
        """
        with _maybe_span(
            self.span_recorder, "prefetch_upload", "stream",
            round_idx=self.span_round,
        ) as _sp:
            return self._upload_body(idx_list, _sp)

    def _upload_body(self, idx_list, _sp):
        t0 = clock.monotonic()
        x, y, m, s = self.store.gather_data(idx_list[0])
        # idx None = the whole population (upload_full): the round
        # program's idx operand stays None too.
        idx_arr = (
            None if idx_list[0] is None
            else np.asarray(idx_list[0], dtype=np.int32)
        )
        host_arrays = (x, y, m, s, idx_arr)
        # The cohort axis leads: the mesh placement shards exactly that
        # axis (PartitionSpec layout).
        arrays = tuple(
            None if a is None else self._placed(a) for a in host_arrays
        )
        # device_put is asynchronous; the transfer is only DONE here —
        # which is the point: this block runs on the worker thread, so at
        # steady state the wait overlaps the main thread's dispatch.
        jax.block_until_ready(arrays)
        nbytes = _nbytes(host_arrays)
        if _sp is not None:
            _sp["bytes"] = nbytes
        return arrays, nbytes, clock.monotonic() - t0

    def prefetch(self, idx_list) -> None:
        """Schedule the upload for the NEXT dispatch's cohorts; returns
        immediately. At most one prefetch is in flight (a second call
        before acquire drains the first — the pipeline is strictly
        double-buffered)."""
        if self._pending is not None:
            # Shouldn't happen in the dispatch loop's sequencing; drain
            # rather than leak a future.
            self._pending[1].result()
            self._pending = None
        self._pending = (
            idx_list, self._pool.submit(self._upload, idx_list)
        )

    def acquire(self, idx_list):
        """Collect the upload for ``idx_list``, preferring the prefetched
        one. Returns ``((x, y, m, sizes, idx_dev), stats)`` where stats
        is this upload's contribution to the stream record."""
        arrays = None
        if self._pending is not None:
            pend_idx, fut = self._pending
            self._pending = None
            if (
                len(pend_idx) == len(idx_list)
                and all(
                    np.array_equal(a, b)
                    for a, b in zip(pend_idx, idx_list)
                )
            ):
                t0 = clock.monotonic()
                arrays, nbytes, dt = fut.result()
                blocked = clock.monotonic() - t0
                hidden = max(dt - blocked, 0.0)
            else:
                # A cohort the loop no longer wants (resume/preemption
                # path changed the sequence): drain and re-upload. The
                # stale transfer still moved real bytes over the bus —
                # count it in the run totals (as unhidden time) so the
                # accounting never under-reports traffic.
                _, stale_bytes, stale_dt = fut.result()
                self.totals["h2d_bytes"] += stale_bytes
                self.totals["h2d_seconds"] += stale_dt
        if arrays is None:
            arrays, nbytes, dt = self._upload(idx_list)
            hidden = 0.0
        self.totals["h2d_bytes"] += nbytes
        self.totals["h2d_seconds"] += dt
        self.totals["hidden_seconds"] += hidden
        stats = {
            "h2d_bytes": nbytes,
            "h2d_seconds": round(dt, 6),
            "hidden_seconds": round(hidden, 6),
            "overlap_ratio": round(hidden / dt, 4) if dt > 0 else 0.0,
        }
        if any(idx is not None for idx in idx_list):
            # Sampled cohorts: name the sampler and drain the pending
            # cohort-replay seconds into this dispatch's record (the
            # host cost the phase table's ``sample`` phase carries).
            stats["sampler"] = self._sampler
            stats["sample_ms"] = round(self._sample_pending * 1e3, 3)
            self._sample_pending = 0.0
        return arrays, stats

    def upload_full(self):
        """One-shot upload of the WHOLE population (the degenerate
        full-cohort regime: participation_fraction >= 1, e.g. sign_SGD's
        per-step vote over everyone). The arrays stay device-resident for
        the run — streamed residency then only moves WHERE the startup
        upload is accounted."""
        arrays, nbytes, dt = self._upload([None])
        self.totals["h2d_bytes"] += nbytes
        self.totals["h2d_seconds"] += dt
        stats = {
            "h2d_bytes": nbytes,
            "h2d_seconds": round(dt, 6),
            "hidden_seconds": 0.0,
            "overlap_ratio": 0.0,
        }
        return arrays, stats

    # ---- writeback ---------------------------------------------------------
    def writeback(self, idx, new_state_k, stats: dict | None = None):
        """Fetch the round's cohort state to host and scatter it into the
        store (Algorithm.scatter_client_state). No-op for stateless
        algorithms. ``stats`` (an acquire stats dict) grows the d2h
        fields in place when given."""
        if self.store.state is None:
            return
        t0 = clock.monotonic()
        host_state = jax.device_get(new_state_k)
        self._algorithm.scatter_client_state(self.store, idx, host_state)
        dt = clock.monotonic() - t0
        nbytes = tree_bytes(host_state)
        self.totals["d2h_bytes"] += nbytes
        self.totals["d2h_seconds"] += dt
        if stats is not None:
            stats["d2h_bytes"] = nbytes
            stats["d2h_seconds"] = round(dt, 6)

    # ---- reporting ---------------------------------------------------------
    def overlap_ratio(self) -> float:
        """Run-total hidden-transfer fraction: how much of the host->HBM
        upload time the prefetch hid behind compute."""
        total = self.totals["h2d_seconds"]
        return self.totals["hidden_seconds"] / total if total > 0 else 0.0

    def close(self) -> None:
        if self._pending is not None:
            # Never leak a worker-thread upload past the run.
            try:
                self._pending[1].result()
            except Exception:
                pass
            self._pending = None
        self._pool.shutdown(wait=True)


# --- distributed shard store: the multihost streamer ------------------------


def _pad_rows(a: np.ndarray, rows: int) -> np.ndarray:
    """Zero-pad a row payload to the exchange's common row count."""
    if a.shape[0] == rows:
        return a
    out = np.zeros((rows,) + a.shape[1:], a.dtype)
    out[: a.shape[0]] = a
    return out


def _pad_bucket(n: int) -> int:
    """Round a spill row count up to the next power of two.

    The allgather compiles one tiny program per distinct payload shape;
    bucketing bounds the distinct shapes at log2(cohort) over a whole
    run instead of one per distinct per-round spill count.
    """
    return 1 << max(int(n) - 1, 0).bit_length()


class _ExecPlan:
    """One round's owner-sharded assembly, resolved for THIS host.

    Wraps the global :class:`data.residency.AssemblyPlan` (identical on
    every host) with this host's derived routing — which of its block
    rows hold its own members, where each spill-in row comes from in
    the forward exchange, and where each of its spilled-out members
    sits for the writeback return trip — plus the assembled host-side
    data block once :meth:`DistributedCohortStreamer.plan` has run the
    exchange.
    """

    def __init__(self, plan, host_id: int):
        self.plan = plan
        self.idx = plan.idx
        self.blo = int(plan.block_bounds[host_id])
        self.bhi = int(plan.block_bounds[host_id + 1])
        occupants_q = plan.draw_pos[self.blo:self.bhi]
        own = plan.owners[occupants_q] == host_id
        self.own_rows_rel = np.flatnonzero(own)
        self.own_ids = plan.idx[occupants_q[own]]
        # Spill-in: rows of MY block served by other hosts' members.
        sel_in = plan.spill_block == host_id
        self.in_rows_rel = plan.spill_rows[sel_in] - self.blo
        self.in_src_host = plan.spill_owner[sel_in]
        self.in_src_slot = plan.slot_in_owner[sel_in]
        # Spill-out: MY members placed in other hosts' blocks.
        sel_out = plan.spill_owner == host_id
        self.out_ids = plan.spill_ids[sel_out]
        self.out_block = plan.spill_block[sel_out]
        self.out_slot = plan.slot_in_block[sel_out]
        self.total_spill = int(plan.spill_q.size)
        self.pad_fwd = _pad_bucket(int(plan.send_counts().max()))
        self.pad_back = _pad_bucket(int(plan.recv_counts().max()))
        self.data_block = None  # filled by DistributedCohortStreamer.plan
        self.dcn_bytes = 0
        self.assemble_seconds = 0.0


class DistributedCohortStreamer(CohortStreamer):
    """Owner-sharded cohort assembly across host processes.

    The multihost face of streamed residency: the full-N client arrays
    live host-SHARDED (each process owns an N/num_hosts slice —
    data/residency.DistributedShardStore), the hashed sampler's
    round-key-determinism lets every host replay the FULL cohort
    independently, and each round's cohort is permuted into
    owner-contiguous groups aligned with the hosts' addressable shards
    of the client-axis ``PartitionSpec``
    (data/residency.plan_owner_assembly). Each host then serves its own
    members straight into its addressable shards via
    ``jax.make_array_from_single_device_arrays`` — no full-N array ever
    crosses DCN; the only cross-host client data is the per-round
    ownership-imbalance spill (expected O(sqrt(cohort)) rows), moved by
    a padded ``process_allgather`` and byte-counted into ``dcn_bytes``.
    The ``draw_pos`` operand the upload carries lets the round program
    permute its per-position draws back to the draw-order assignment
    (algorithms/fedavg.cohort_round), which is what keeps the
    owner-permuted run equal to the 1-process run per client.

    Threading contract: the spill exchange is a COLLECTIVE, so it runs
    on the MAIN thread (inside :meth:`plan`, which the round loop calls
    at the same point on every host); the worker thread only does the
    local ``device_put`` assembly — collective launch order therefore
    stays identical across processes, which is what keeps concurrent
    prefetch deadlock-free.
    """

    def __init__(self, store, algorithm, n_clients: int, mesh,
                 block_bounds):
        super().__init__(store, algorithm, n_clients, mesh=mesh)
        self._host = store.host_id
        self._n_hosts = store.n_hosts
        self._block_bounds = np.asarray(block_bounds, np.int64)
        self._cohort = int(self._block_bounds[-1])
        self.totals.update({"dcn_bytes": 0, "spill_rows": 0})

    # ---- exchange ----------------------------------------------------------
    def _allgather(self, leaves, pad: int, name: str = "spill"):
        """Padded all-to-all of per-host row payloads: every host
        contributes ``pad`` rows per leaf (zeros beyond its real send
        count — every host knows every count from the shared plan, so
        no negotiation); returns leaves of shape ``[n_hosts, pad, ...]``.
        Collective — main thread only.

        With a span recorder attached, the exchange splits into a
        ``<name>_wait`` span (a tiny arrival-stamp allgather: its
        duration is dominated by the SLOWEST host's arrival, and the
        gathered aligned stamps yield the round's measured barrier skew)
        and a ``<name>_xfer`` span (the payload allgather proper). The
        wait span is flight-recorder eager: a host stuck here during a
        peer's death leaves its open-line on disk for the postmortem.
        """
        from jax.experimental import multihost_utils

        from distributed_learning_simulator_tpu.parallel.multihost import (
            allgather_wall_stamps,
        )

        _maybe_straggle()
        rec = self.span_recorder
        if rec is not None:
            with rec.span(
                f"{name}_wait", "dcn_wait", round_idx=self.span_round,
                eager=True,
            ) as w:
                stamps = allgather_wall_stamps(
                    clock.wall() - self.clock_offset_s
                )
                skew_ms = float(stamps.max() - stamps.min()) * 1e3
                w["skew_ms"] = round(skew_ms, 3)
            if self.span_round is not None:
                rec.note_skew(self.span_round, "spill_skew_ms", skew_ms)
        padded = tuple(_pad_rows(np.asarray(a), pad) for a in leaves)
        with _maybe_span(
            rec, f"{name}_xfer", "dcn", round_idx=self.span_round,
        ) as x:
            gathered = multihost_utils.process_allgather(
                padded, tiled=False
            )
            nbytes = sum(int(g.nbytes) for g in gathered)
            if x is not None:
                x["bytes"] = nbytes
        self.totals["dcn_bytes"] += nbytes
        return list(gathered), nbytes

    def _assemble_block(self, ex: _ExecPlan, local_leaves):
        """Fill this host's block rows for each leaf: own members from
        the local shard, spill-in rows from the forward exchange."""
        own_local = self.store.to_local(ex.own_ids)
        send_local = self.store.to_local(
            ex.out_ids
        ) if ex.out_ids.size else np.empty(0, np.int64)
        gathered = None
        if ex.total_spill:
            send = [
                np.take(np.asarray(a), send_local, axis=0)
                for a in local_leaves
            ]
            gathered, nbytes = self._allgather(send, ex.pad_fwd)
            ex.dcn_bytes += nbytes
        out = []
        for li, a in enumerate(local_leaves):
            a = np.asarray(a)
            blk = np.empty(
                (ex.bhi - ex.blo,) + a.shape[1:], a.dtype
            )
            if ex.own_rows_rel.size:
                blk[ex.own_rows_rel] = np.take(a, own_local, axis=0)
            if ex.in_rows_rel.size:
                blk[ex.in_rows_rel] = gathered[li][
                    ex.in_src_host, ex.in_src_slot
                ]
            out.append(blk)
        return out

    # ---- planning ----------------------------------------------------------
    def plan(self, idx_np) -> _ExecPlan:
        """Resolve one round's owner-sharded assembly: the global
        row-assignment plan, plus this host's data block with spill-in
        rows exchanged. Main thread (the exchange is a collective)."""
        t0 = clock.monotonic()
        p = plan_owner_assembly(
            np.asarray(idx_np, np.int64), self.store.owner_bounds,
            self._block_bounds,
        )
        ex = _ExecPlan(p, self._host)
        ex.data_block = self._assemble_block(
            ex, [self.store.x, self.store.y, self.store.mask,
                 self.store.sizes],
        )
        ex.assemble_seconds = clock.monotonic() - t0
        self.totals["spill_rows"] += ex.total_spill
        return ex

    # ---- placement ---------------------------------------------------------
    def _place_block(self, block: np.ndarray, global_len: int, blo: int,
                     owned: bool = False):
        """This host's block rows -> its addressable shards of the
        client-axis PartitionSpec, assembled into one global array via
        jax.make_array_from_single_device_arrays (the only constructor
        that lets each process contribute exactly the rows it holds).

        ``owned=True`` forces XLA-owned shard buffers: device_put of a
        numpy slice is zero-copy on the CPU backend, and a DONATED
        operand backed by numpy-owned memory lets XLA write into (and
        free) host memory — the `_owned_device_tree` hazard, observed
        here as intermittent garbage part_sizes blowing up the round
        aggregate. Required for the state tree (round_jit donates it);
        the data blocks stay zero-copy (non-donated, and the plan keeps
        their numpy backing alive through the dispatch)."""
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        gshape = (global_len,) + block.shape[1:]
        spec = PartitionSpec(
            self._mesh.axis_names[0], *([None] * (block.ndim - 1))
        )
        sharding = NamedSharding(self._mesh, spec)
        arrs = []
        for d, idxs in sharding.addressable_devices_indices_map(
            gshape
        ).items():
            sl = idxs[0]
            start = 0 if sl.start is None else sl.start
            stop = global_len if sl.stop is None else sl.stop
            local = block[start - blo: stop - blo]
            if owned:
                with jax.default_device(d):
                    arrs.append(jnp.array(local, copy=True))
            else:
                arrs.append(jax.device_put(local, d))
        return jax.make_array_from_single_device_arrays(
            gshape, sharding, arrs
        )

    def _replicated(self, a):
        """Replicated placement WITHOUT jax.device_put's cross-process
        value check: device_put against a non-addressable sharding runs
        a hidden assert_equal COLLECTIVE, and this is called from the
        worker thread — a collective there would race the main thread's
        (round dispatch / exchange) collectives and deadlock the hosts.
        Each local device gets the full value (identical on every host
        by construction: the plan is a pure function of the replayed
        cohort), assembled locally."""
        from jax.sharding import NamedSharding, PartitionSpec

        a = np.asarray(a)
        sharding = NamedSharding(self._mesh, PartitionSpec())
        arrs = [
            jax.device_put(a, d) for d in sharding.addressable_devices
        ]
        return jax.make_array_from_single_device_arrays(
            a.shape, sharding, arrs
        )

    def _upload_plan(self, ex: _ExecPlan):
        """Worker-thread body: local device_put assembly only (the
        exchange already ran in plan(), on the main thread)."""
        with _maybe_span(
            self.span_recorder, "prefetch_upload", "stream",
            round_idx=self.span_round,
        ) as _sp:
            return self._upload_plan_body(ex, _sp)

    def _upload_plan_body(self, ex: _ExecPlan, _sp):
        t0 = clock.monotonic()
        blo = int(self._block_bounds[self._host])
        x, y, m, s = (
            self._place_block(b, self._cohort, blo) for b in ex.data_block
        )
        sidx = self._replicated(np.asarray(ex.plan.idx_perm, np.int32))
        dpos = self._replicated(np.asarray(ex.plan.draw_pos, np.int32))
        arrays = (x, y, m, s, sidx, dpos)
        jax.block_until_ready(arrays)
        nbytes = sum(int(b.nbytes) for b in ex.data_block) + int(
            ex.plan.idx_perm.nbytes + ex.plan.draw_pos.nbytes
        )
        if _sp is not None:
            _sp["bytes"] = nbytes
        return arrays, nbytes, clock.monotonic() - t0

    # ---- upload / prefetch (plan-keyed double buffering) -------------------
    def prefetch_plan(self, ex: _ExecPlan) -> None:
        if self._pending is not None:
            self._pending[1].result()
            self._pending = None
        self._pending = (ex, self._pool.submit(self._upload_plan, ex))

    def acquire_plan(self, ex: _ExecPlan):
        """Collect the upload for ``ex``, preferring the prefetched one
        (same double-buffer semantics as the base acquire, keyed by the
        plan's cohort)."""
        arrays = None
        if self._pending is not None:
            pend_ex, fut = self._pending
            self._pending = None
            if pend_ex is ex or np.array_equal(pend_ex.idx, ex.idx):
                t0 = clock.monotonic()
                arrays, nbytes, dt = fut.result()
                blocked = clock.monotonic() - t0
                hidden = max(dt - blocked, 0.0)
                ex = pend_ex
            else:
                _, stale_bytes, stale_dt = fut.result()
                self.totals["h2d_bytes"] += stale_bytes
                self.totals["h2d_seconds"] += stale_dt
        if arrays is None:
            arrays, nbytes, dt = self._upload_plan(ex)
            hidden = 0.0
        self.totals["h2d_bytes"] += nbytes
        self.totals["h2d_seconds"] += dt
        self.totals["hidden_seconds"] += hidden
        stats = {
            "h2d_bytes": nbytes,
            "h2d_seconds": round(dt, 6),
            "hidden_seconds": round(hidden, 6),
            "overlap_ratio": round(hidden / dt, 4) if dt > 0 else 0.0,
            "sampler": self._sampler,
            "sample_ms": round(self._sample_pending * 1e3, 3),
            "spill_rows": ex.total_spill,
            "dcn_bytes": ex.dcn_bytes,
        }
        self._sample_pending = 0.0
        return arrays, stats, ex

    # ---- persistent per-client state ---------------------------------------
    def gather_state_device(self, ex: _ExecPlan):
        """Assemble this host's block of the cohort's persistent state
        (own rows from the local shard, spill-in rows exchanged) and
        place it into the client-axis PartitionSpec layout. None for
        stateless algorithms. Main thread (collective)."""
        if self.store.state is None:
            return None
        from distributed_learning_simulator_tpu.data.residency import (
            tree_map_np,
        )

        leaves, treedef = jax.tree_util.tree_flatten(
            tree_map_np(np.asarray, self.store.state)
        )
        blocks = self._assemble_block(ex, leaves)
        blo = int(self._block_bounds[self._host])
        placed = [
            self._place_block(b, self._cohort, blo, owned=True)
            for b in blocks
        ]
        return jax.tree_util.tree_unflatten(treedef, placed)

    def writeback(self, ex, new_state_k, stats: dict | None = None):
        """Scatter the round's cohort state back to its OWNERS: each
        host fetches its addressable output shards, keeps its own
        members' rows, and returns the spill rows to their owning hosts
        through the reverse exchange. Main thread (collective)."""
        if self.store.state is None:
            return
        t0 = clock.monotonic()

        def local_rows(leaf):
            shards = sorted(
                leaf.addressable_shards,
                key=lambda s: s.index[0].start or 0,
            )
            return np.concatenate(
                [np.asarray(s.data) for s in shards], axis=0
            )

        host_state = jax.tree_util.tree_map(local_rows, new_state_k)
        leaves, treedef = jax.tree_util.tree_flatten(host_state)
        if ex.own_ids.size:
            own_tree = jax.tree_util.tree_unflatten(
                treedef, [l[ex.own_rows_rel] for l in leaves]
            )
            self._algorithm.scatter_client_state(
                self.store, ex.own_ids, own_tree
            )
        dcn = 0
        if ex.total_spill:
            send = [l[ex.in_rows_rel] for l in leaves]
            gathered, dcn = self._allgather(
                send, ex.pad_back, name="writeback"
            )
            if ex.out_ids.size:
                mine = [
                    g[ex.out_block, ex.out_slot] for g in gathered
                ]
                self._algorithm.scatter_client_state(
                    self.store, ex.out_ids,
                    jax.tree_util.tree_unflatten(treedef, mine),
                )
        dt = clock.monotonic() - t0
        nbytes = sum(int(l.nbytes) for l in leaves)
        self.totals["d2h_bytes"] += nbytes
        self.totals["d2h_seconds"] += dt
        if stats is not None:
            stats["d2h_bytes"] = nbytes
            stats["d2h_seconds"] = round(dt, 6)
            stats["dcn_bytes"] = stats.get("dcn_bytes", 0) + dcn

    # ---- full-cohort regime ------------------------------------------------
    def upload_full(self):
        """One-shot whole-population upload: each host places its OWNED
        slice into its addressable shards of the full-N client axis
        (owner bounds are the device blocks by construction —
        data/residency.host_axis_bounds). Zero DCN traffic."""
        t0 = clock.monotonic()
        x, y, m, s = self.store.gather_data(None)
        n = int(self.store.owner_bounds[-1])
        arrays = tuple(
            self._place_block(np.asarray(a), n, self.store.lo)
            for a in (x, y, m, s)
        ) + (None,)
        jax.block_until_ready([a for a in arrays if a is not None])
        nbytes = self.store.data_bytes()
        dt = clock.monotonic() - t0
        self.totals["h2d_bytes"] += nbytes
        self.totals["h2d_seconds"] += dt
        stats = {
            "h2d_bytes": nbytes,
            "h2d_seconds": round(dt, 6),
            "hidden_seconds": 0.0,
            "overlap_ratio": 0.0,
        }
        return arrays, stats

    # ---- reporting ---------------------------------------------------------
    def multihost_record(self, ex: _ExecPlan | None, stats: dict) -> dict:
        """The schema-v11 ``multihost`` record sub-object: this host's
        shard-ownership summary plus the round's assembly traffic
        (utils/reporting.build_round_record routes it)."""
        shard_bytes = self.store.data_bytes()
        if self.store.state is not None:
            shard_bytes += self.store.state_bytes()
        return {
            "hosts": self._n_hosts,
            "host_id": self._host,
            "owned_clients": self.store.n_owned,
            "shard_bytes": int(shard_bytes),
            "spill_rows": int(ex.total_spill) if ex is not None else 0,
            "dcn_bytes": int(stats.get("dcn_bytes", 0)),
            "h2d_seconds": stats.get("h2d_seconds", 0.0),
            "overlap_ratio": stats.get("overlap_ratio", 0.0),
        }
