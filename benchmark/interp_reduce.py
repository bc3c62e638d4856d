"""From the program's interpreter account to per-layer numbers: the
arithmetic the ``interp.*``, ``log.*`` and ``*_cpu_*`` readers under
``layer_metrics/`` share. The program keeps, in ONE node's registry of a
snapshot (so a sum over the nodes counts the one process once), gauges
``interp.cpu_s.<role>`` (CPU seconds of the threads of that role since the
process began, monotone) and counters ``log.lines_total`` /
``log.emit_s_total``; its batch-level spans and ``client:submit`` carry
``cpu_s``. A delta is the window's: ``run.metrics_end`` less
``run.metrics_start``. A program without the account (an older commit)
gives None everywhere.
"""
from __future__ import annotations

from typing import Callable, Iterable, List, Optional

from . import span_reduce

CPU_PREFIX = "interp.cpu_s."


def _delta(run, kind: str, keep: Callable[[str], bool]) -> Optional[float]:
    """End less start of the ``kind`` entries whose name ``keep`` accepts,
    summed over the nodes; None where the end has none."""
    total, found = 0.0, False
    for nid, snap in run.metrics_end.items():
        start = run.metrics_start.get(nid, {}).get(kind, {})
        for name, value in snap.get(kind, {}).items():
            if keep(name):
                found = True
                total += value - start.get(name, 0.0)
    return total if found else None


def cpu_delta_s(run, roles: Optional[Iterable[str]] = None) -> Optional[float]:
    """CPU seconds the threads of ``roles`` (every role where None) ran
    inside the window."""
    if roles is None:
        return _delta(run, "gauges", lambda n: n.startswith(CPU_PREFIX))
    wanted = {CPU_PREFIX + r for r in roles}
    return _delta(run, "gauges", wanted.__contains__)


def counter_delta(run, name: str) -> Optional[float]:
    return _delta(run, "counters", name.__eq__)


def per_sign_ms(run, seconds: Optional[float]) -> Optional[float]:
    """``seconds`` shared among the window's requests, in ms."""
    signs = len(run.measured)
    if seconds is None or not signs:
        return None
    return seconds / signs * 1e3


def cpu_spans(run, names: Iterable[str]) -> List[dict]:
    """The window's spans of ``names`` that carry ``cpu_s``."""
    names = set(names)
    return [s for s in span_reduce.window_spans(run, lambda n: n in names)
            if "cpu_s" in (s.get("attrs") or {})]
