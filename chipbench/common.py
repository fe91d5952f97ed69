"""Small helpers the drivers share."""

from __future__ import annotations

import math
import time


def now() -> float:
    return time.perf_counter()


def percentile(values, q: float) -> float:
    """Nearest-rank percentile; +inf entries rank highest."""
    v = sorted(values)
    if not v:
        return math.nan
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def memory_peak_bytes(devices) -> int:
    """Per chip, the live arrays' peak plus the region reserved for the
    executables' temporaries; the fullest chip's."""
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0))
                   + int(st.get("peak_bytes_reserved", 0)))
    return peak


def checks(limits: dict, readings: dict) -> dict:
    """Each compared number beside its limit; a missing reading fails."""
    return {k: {"value": readings.get(k, math.inf), "limit": lim["limit"]}
            for k, lim in limits.items()}


def passed(ch: dict) -> bool:
    return bool(ch) and all(c["value"] <= c["limit"] for c in ch.values())
