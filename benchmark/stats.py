"""Arithmetic of the end-to-end metrics: nearest-rank percentiles and the
window's throughput. Pure functions over host-clock readings; no JAX.

The percentile is the nearest-rank rule of ``mpcium_tpu/soak.py`` (``_pct``),
copied here so the program cannot move the yardstick.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Sequence


def percentile(values: Iterable[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q % of
    the sample at or below it. ``q`` in (0, 100]. Raises on no samples."""
    s = sorted(values)
    if not s:
        raise ValueError("percentile of an empty sample")
    if not 0 < q <= 100:
        raise ValueError(f"q must be in (0, 100], got {q}")
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return s[rank - 1]


def latencies_ms(
    submit_ns: Sequence[int],
    done_ns: Sequence[int | None],
    ok: Sequence[bool],
    window_ns: int,
) -> List[float]:
    """Per-request latency from the client's call to its result event. A
    request that failed, or never came back, counts as the whole window
    (it has missed any limit a user could set)."""
    out = []
    for t0, t1, good in zip(submit_ns, done_ns, ok):
        if good and t1 is not None:
            out.append((t1 - t0) / 1e6)
        else:
            out.append(window_ns / 1e6)
    return out


def throughput(done_ns: Sequence[int | None], ok: Sequence[bool],
               window_start_ns: int) -> float:
    """Good completions per second, over the time from the window's start
    to the last counted completion: all the work and all the time of the
    window, continuous in speed (no whole-wave steps)."""
    good = [t for t, g in zip(done_ns, ok) if g and t is not None]
    if not good:
        return 0.0
    span = max(good) - window_start_ns
    if span <= 0:
        raise ValueError("a completion precedes the window's start")
    return len(good) / (span / 1e9)


def growth_pct(wave_seconds: Sequence[float]) -> float | None:
    """Last wave's seconds over the first's, minus one, in percent."""
    if len(wave_seconds) < 2:
        return None
    return (wave_seconds[-1] / wave_seconds[0] - 1.0) * 100.0
