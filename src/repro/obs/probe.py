"""Peak-memory probes (host RSS + device allocator high-water mark).

Lives in ``repro.obs`` so library code — ``StreamStats.as_row()``, the
report — can record residency without importing bench helpers;
``benchmarks.common`` re-exports :func:`memory_probe` for the existing
figure scripts.
"""
from __future__ import annotations

__all__ = ["memory_probe", "device_peak_bytes"]


def memory_probe() -> dict:
    """Peak-memory observability hook for the out-of-core tier.

    Returns ``host_peak_rss_bytes`` (the process high-water mark — on
    Linux ``ru_maxrss`` is KiB) and ``device_peak_bytes`` (the first
    device's allocator high-water mark, ``None`` where the platform
    doesn't report one, e.g. CPU jax). fig11's oversubscription rows and
    the CI stream gate record both next to the modeled ring bytes, so a
    residency regression shows up as measured numbers, not just model
    drift.
    """
    probe: dict = {"host_peak_rss_bytes": None,
                   "device_peak_bytes": device_peak_bytes()}
    try:
        import resource
        import sys

        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        scale = 1024 if sys.platform.startswith("linux") else 1
        probe["host_peak_rss_bytes"] = int(peak) * scale
    except (ImportError, ValueError, OSError):
        pass
    return probe


def device_peak_bytes() -> int | None:
    """First device's allocator high-water mark (``None`` when the
    platform reports no memory stats — e.g. CPU jax)."""
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use", stats.get("bytes_in_use"))
    except Exception:  # memory_stats unsupported on this backend
        return None
