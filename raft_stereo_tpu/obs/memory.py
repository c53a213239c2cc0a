"""Device memory telemetry: per-device `memory_stats()` + live buffers.

`Device.memory_stats()` is a host-side call into the PJRT client — it
reports allocator state (bytes_in_use, peak_bytes_in_use, bytes_limit)
without dispatching device work or syncing any computation, so sampling
it per serving batch / per training save boundary keeps the
zero-sync/zero-executable hot-path contract intact. On CPU the method is
absent or returns None/empty; the block degrades to zeros with
`available: false` — callers (healthz, chip_smoke.py, prom gauges) always
get the same typed shape, on the CPU and on the chip.

`jax.live_arrays()` walks the host-side registry of live jax.Array
objects (again no device traffic); its count + nbytes total is the
"what is actually resident" complement to the allocator view.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

# Keys lifted from a device's memory_stats() dict when present. PJRT
# backends vary; the first three are the common core the healthz block
# standardizes on. `peak_bytes_reserved` is the TPU runtime's: a program's
# temporaries show only there, not in `peak_bytes_in_use` (a Middlebury-F
# forward: 0.3 GB in use against 5.05 GB reserved, chip run, PR 22).
_STAT_KEYS = ("bytes_in_use", "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")


def sample_device_memory() -> List[Dict[str, Any]]:
    """Per-local-device allocator stats; empty list when the backend
    exposes none (CPU). Never raises — telemetry must not take down the
    path it observes."""
    try:
        import jax

        devices = jax.local_devices()
    except Exception:  # noqa: BLE001 - no backend at all
        return []
    out: List[Dict[str, Any]] = []
    for d in devices:
        stats_fn = getattr(d, "memory_stats", None)
        if stats_fn is None:
            continue
        try:
            stats = stats_fn()
        except Exception:  # noqa: BLE001 - backend without allocator stats
            stats = None
        if not stats:
            continue
        entry: Dict[str, Any] = {"device": str(getattr(d, "id", len(out)))}
        for key in _STAT_KEYS:
            entry[key] = int(stats.get(key, 0))
        out.append(entry)
    return out


def _live_buffers() -> Dict[str, int]:
    try:
        import jax

        arrays = jax.live_arrays()
    except Exception:  # noqa: BLE001 - API absent or backend-less
        return {"live_buffer_count": 0, "live_buffer_bytes": 0}
    count = 0
    total = 0
    for a in arrays:
        count += 1
        try:
            total += int(getattr(a, "nbytes", 0) or 0)
        except Exception:  # noqa: BLE001 - deleted under our feet
            pass
    return {"live_buffer_count": count, "live_buffer_bytes": total}


def memory_block(devices: Optional[List[Dict[str, Any]]] = None) -> Dict[str, Any]:
    """The typed `memory` block of /healthz. Sums the per-device view;
    always complete, zeros + available=false on CPU."""
    if devices is None:
        devices = sample_device_memory()
    block: Dict[str, Any] = {
        "available": bool(devices),
        "device_count": len(devices),
        "bytes_in_use": sum(int(d.get("bytes_in_use", 0)) for d in devices),
        "peak_bytes_in_use": sum(int(d.get("peak_bytes_in_use", 0)) for d in devices),
        "peak_bytes_reserved": sum(int(d.get("peak_bytes_reserved", 0)) for d in devices),
        "bytes_limit": sum(int(d.get("bytes_limit", 0)) for d in devices),
    }
    block.update(_live_buffers())
    return block


def set_memory_gauges(registry, prefix: str = "raft") -> Dict[str, Any]:
    """Sample and publish the memory block into prom gauges. Returns the
    sampled block so callers can also stash it (healthz caches the last
    per-batch sample rather than re-walking live arrays per scrape)."""
    block = memory_block()
    registry.gauge(
        f"{prefix}_device_memory_bytes_in_use",
        "Sum of per-device allocator bytes_in_use (0 when unavailable)",
    ).set(block["bytes_in_use"])
    registry.gauge(
        f"{prefix}_device_memory_peak_bytes_in_use",
        "Sum of per-device allocator peak_bytes_in_use",
    ).set(block["peak_bytes_in_use"])
    registry.gauge(
        f"{prefix}_device_memory_peak_bytes_reserved",
        "Sum of per-device peak_bytes_reserved (program temporaries included; 0 where the backend has none)",
    ).set(block["peak_bytes_reserved"])
    registry.gauge(
        f"{prefix}_device_memory_bytes_limit",
        "Sum of per-device allocator bytes_limit",
    ).set(block["bytes_limit"])
    registry.gauge(
        f"{prefix}_live_buffer_count", "Live jax.Array count on this host"
    ).set(block["live_buffer_count"])
    registry.gauge(
        f"{prefix}_live_buffer_bytes", "Total nbytes of live jax.Arrays"
    ).set(block["live_buffer_bytes"])
    registry.gauge(
        f"{prefix}_device_memory_available",
        "1 when the backend exposes allocator stats (TPU/GPU), 0 on CPU",
    ).set(1.0 if block["available"] else 0.0)
    return block
