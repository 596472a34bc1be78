"""HBM watermark sampling: the ONE ``memory_stats()`` probe.

Device memory statistics come from the PJRT plugin and are optional —
CPU returns ``None``, and some plugin versions omit individual keys.
Every consumer (the round loop's per-round watermark, the chunk
auto-sizer's budget model, scripts/measure_gtg_scale.py) goes through
these helpers so the graceful-``None`` contract lives in one place.
"""

from __future__ import annotations

import jax


def device_memory_stats(device=None) -> dict | None:
    """Raw ``memory_stats()`` dict for ``device`` (default: first local
    device), or ``None`` when the backend doesn't report memory stats."""
    try:
        if device is None:
            device = jax.local_devices()[0]
        stats = device.memory_stats()
    except Exception:
        return None
    return dict(stats) if stats else None


def peak_hbm_bytes(device=None) -> int | None:
    """High-water mark of device memory in use (``peak_bytes_in_use``),
    or ``None`` when unavailable. On TPU this is cumulative since process
    start — per-round samples are monotone, and the per-run watermark is
    the last round's value."""
    stats = device_memory_stats(device)
    if not stats:
        return None
    peak = stats.get("peak_bytes_in_use")
    return int(peak) if peak else None


def hbm_limit_bytes(device=None) -> int | None:
    """Usable device memory capacity (``bytes_limit``), or ``None`` when
    unavailable. Feeds :func:`device_memory_limit`."""
    stats = device_memory_stats(device)
    if not stats:
        return None
    limit = stats.get("bytes_limit")
    return int(limit) if limit else None


def device_memory_limit() -> tuple[int, str]:
    """Per-device memory capacity and where the number came from.

    An accelerator reports ``bytes_limit``; one that does not is an
    error, not a guess. Only the CPU backend (which reports no memory
    stats) gets an assumed 16 GiB, so the CPU tests size chunks, and
    choose which blocks to rematerialise, the way a v5e run would."""
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
