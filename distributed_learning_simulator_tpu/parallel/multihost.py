"""Multi-host (DCN) initialization.

The reference's dormant multi-process path (``TorchProcessTaskQueue``,
reference servers/server.py:11-13, hard-disabled at simulator.py:56) is the
closest it gets to multi-node. The TPU-native equivalent: initialize the JAX
distributed runtime, after which ``jax.devices()`` spans every host's chips
and the SAME mesh/sharding code (parallel/mesh.py) runs the client axis over
ICI within a slice and DCN across slices — no separate code path.
"""

from __future__ import annotations

import jax
import numpy as np

from distributed_learning_simulator_tpu.utils.logging import get_logger

# Coordinator this process successfully initialized against (None when
# jax.distributed was brought up elsewhere or auto-configured) — the JAX
# API doesn't expose it, so remember it to catch a re-call that names a
# DIFFERENT coordinator while counts happen to match.
_initialized_coordinator: str | None = None


def initialize_multihost(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> int:
    """Initialize jax.distributed; returns the global device count.

    With no arguments, relies on the TPU environment's auto-configuration
    (the standard path on Cloud TPU pods); in a plain single-process
    environment that raises (nothing to auto-detect) and degrades to a
    logged no-op, so one binary serves pods and laptops. Safe to call when
    jax.distributed is already initialized (logged no-op, any flags). With
    EXPLICIT coordinator flags and no prior initialization, failures are
    fatal: a misconfigured 2-process launch must not silently split into
    two independent single-process runs that each write a full set of
    artifacts.
    """
    global _initialized_coordinator

    logger = get_logger()
    explicit = any(
        v is not None
        for v in (coordinator_address, num_processes, process_id)
    )
    if jax.distributed.is_initialized():
        # Safe to re-call in an already-distributed process (a second
        # run_simulation in the same driver, a retry) — but explicit flags
        # must MATCH the live topology: reusing a single-process runtime
        # when the caller asked for process 1-of-2 is exactly the silent
        # split this function's contract forbids.
        if explicit:
            if (
                num_processes is not None
                and jax.process_count() != num_processes
            ) or (
                process_id is not None
                and jax.process_index() != process_id
            ):
                raise RuntimeError(
                    "jax.distributed is already initialized as process "
                    f"{jax.process_index()}/{jax.process_count()}, which "
                    "does not match the explicit multihost flags "
                    f"(num_processes={num_processes}, "
                    f"process_id={process_id}); refusing to proceed"
                )
            if coordinator_address is not None:
                if (
                    _initialized_coordinator is not None
                    and _initialized_coordinator != coordinator_address
                ):
                    raise RuntimeError(
                        "jax.distributed is already initialized against "
                        f"coordinator {_initialized_coordinator!r} but the "
                        f"caller asked for {coordinator_address!r}; "
                        "refusing to silently reuse a different cluster"
                    )
                if _initialized_coordinator is None:
                    logger.warning(
                        "jax.distributed was initialized outside "
                        "initialize_multihost; cannot verify it points at "
                        "the requested coordinator %r",
                        coordinator_address,
                    )
        logger.info("jax.distributed already initialized; reusing it")
    else:
        try:
            # CPU backend (tests, CPU clusters): cross-process
            # computations need a CPU collectives implementation —
            # without one, the first sharded dispatch dies with
            # "Multiprocess computations aren't implemented on the CPU
            # backend". Gloo ships in jaxlib; the knob must be set
            # BEFORE the backend initializes, which this call precedes
            # by contract (it runs before any device query). A no-op
            # for TPU/GPU (their collectives ride ICI/NCCL regardless).
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
            )
            if coordinator_address is not None:
                _initialized_coordinator = coordinator_address
        except (RuntimeError, ValueError) as e:
            # No coordinator configured and none auto-detectable (plain
            # single-process environment).
            if explicit:
                raise RuntimeError(
                    "jax.distributed.initialize failed with explicit "
                    "multihost flags (coordinator_address="
                    f"{coordinator_address!r}, "
                    f"num_processes={num_processes}, "
                    f"process_id={process_id}); refusing to degrade to a "
                    "single-process run"
                ) from e
            logger.info(
                "jax.distributed.initialize skipped (single process): %s", e
            )
    n = len(jax.devices())
    logger.info(
        "multihost: process %d/%d, %d global devices",
        jax.process_index(), jax.process_count(), n,
    )
    return n


def allgather_wall_stamps(stamp: float) -> np.ndarray:
    """Gather one wall-clock stamp per host at full precision.

    The naive float gather is silently useless: with x64 disabled (the
    default) every float64 array crossing a collective is cast to
    float32, whose resolution at a ~1.8e9 s Unix epoch is 128 s —
    every host's stamp rounds to the SAME value and measured skews
    read exactly 0.0. Split each stamp into its float32 head plus the
    float64 remainder (|remainder| <= half the head's 128 s ulp, where
    float32 resolution is ~4 µs) and rebuild float64 after the gather:
    microsecond precision through a float32 pipe, well under the
    collective-latency uncertainty floor.

    Returns the ``[n_hosts]`` float64 stamp vector in process order.
    Collective — main thread only.
    """
    from jax.experimental import multihost_utils

    head = np.float32(stamp)
    rest = np.float32(stamp - np.float64(head))
    gathered = np.asarray(multihost_utils.process_allgather(
        np.asarray([head, rest], np.float32)
    )).reshape(-1, 2)
    return (gathered[:, 0].astype(np.float64)
            + gathered[:, 1].astype(np.float64))


def estimate_clock_alignment() -> tuple[float, float]:
    """Estimate this host's wall-clock offset vs host 0, for the span
    journals (telemetry/spans.py headers).

    Runs once, right after :func:`initialize_multihost` — the
    barrier-synchronized moment when every host is provably inside the
    same code region. Two back-to-back ``process_allgather`` barriers:
    each host stamps ``clock.wall()`` immediately after the FIRST
    barrier releases (all hosts release within one collective latency
    of each other), and the SECOND gather publishes the stamps. The
    offset is ``my_stamp - host0_stamp`` (positive = this host's wall
    clock reads ahead of host 0's); the uncertainty is the measured
    barrier release width — the round-trip this host observed across
    the two collectives, an upper bound on how non-simultaneous the
    stamps were. Good to ~collective-latency (µs on ICI, ms on DCN),
    which is exactly the resolution the cross-host timeline needs:
    barrier skews below the collective latency are not attributable
    to hosts anyway.

    Single-process (or uninitialized) runs return ``(0.0, 0.0)``.
    """
    if jax.process_count() <= 1:
        return 0.0, 0.0
    from jax.experimental import multihost_utils

    from distributed_learning_simulator_tpu.telemetry import clock

    # Barrier 1: align all hosts to within one collective latency.
    multihost_utils.process_allgather(np.zeros([1], dtype=np.int32))
    t_release = clock.monotonic()
    stamp = clock.wall()
    # Barrier 2: publish the post-release stamps (split-float gather —
    # a plain float gather collapses to float32 and reads all-equal).
    stamps = allgather_wall_stamps(stamp)
    rtt = clock.monotonic() - t_release
    offset = float(stamp - stamps[0])
    return offset, float(rtt)


def mesh_devices_per_host(mesh) -> list[int]:
    """Per-process device counts of a 1-D mesh, validated for the
    distributed shard store's contiguous-block layout.

    The owner-sharded cohort assembly (data/residency.plan_owner_assembly
    + parallel/streaming.DistributedCohortStreamer) needs each host's
    addressable shards of the client-axis ``PartitionSpec`` to be ONE
    contiguous row block, which holds exactly when the mesh's device
    order groups processes contiguously (true for ``jax.devices()`` on
    every backend — devices sort by process index — but verified here
    rather than assumed). Also requires the mesh to span EVERY process:
    a process with no addressable mesh device could never serve its
    owned clients' rows. Returns ``devices_per_host`` indexed by process
    id — the input :func:`data.residency.host_axis_bounds` turns into
    ownership/block boundaries.
    """
    procs = [d.process_index for d in np.ravel(mesh.devices)]
    n_hosts = jax.process_count()
    if sorted(procs) != procs:
        raise ValueError(
            "mesh device order interleaves processes "
            f"(process sequence {procs}); the distributed shard store "
            "needs each host's mesh shards contiguous — build the mesh "
            "from jax.devices() order"
        )
    counts = [0] * n_hosts
    for p in procs:
        counts[p] += 1
    missing = [h for h, c in enumerate(counts) if c == 0]
    if missing:
        raise ValueError(
            f"mesh spans {len(set(procs))} of {n_hosts} processes "
            f"(processes {missing} contribute no device); "
            "client_residency='streamed' under multihost needs every "
            "host addressable in the mesh — set mesh_devices to the "
            "global device count"
        )
    return counts
