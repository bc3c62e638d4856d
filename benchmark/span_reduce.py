"""From the program's own spans and histograms to per-layer numbers: the
reductions the stage readers under ``layer_metrics/`` share. Spans are the
flight recorder's raw dicts (``name``, ``node``, ``span_id``, ``parent_id``,
``t0_ns``/``t1_ns`` on ``time.monotonic_ns``, ``attrs``); histograms are
the registries' summaries (``sum``, ``count``) at the window's two ends.
A program that lacks a span or a histogram (an older commit) gives None.
"""
from __future__ import annotations

from typing import Callable, List, Optional

from . import trace_reduce

# what the host does, as against what it waits for: ``queue`` (an entry
# waiting in a bucket), ``session`` (a batch's whole life), ``wait:*`` and
# the benchmark's own ``bench:*`` phases name no work
WORK_PREFIXES = ("client:", "intake", "dispatch", "host:", "round:", "phase:")


def window_spans(run, keep: Callable[[str], bool]) -> List[dict]:
    """The finished spans that began inside the measured window."""
    return [s for s in run.spans
            if keep(s["name"]) and s.get("t1_ns") is not None
            and s["t0_ns"] >= run.window_start_ns]


def duration_ms(span: dict) -> float:
    return (span["t1_ns"] - span["t0_ns"]) / 1e6


def ms_per_node_and_wave(run, total_ms: float,
                         spans: List[dict]) -> Optional[float]:
    """``total_ms`` over the nodes that wrote ``spans`` and the measured
    waves: one node's share of one wave."""
    waves = len(run.measured_waves)
    nodes = {s.get("node") for s in spans}
    if not spans or not waves:
        return None
    return total_ms / len(nodes) / waves


def stage_ms_per_wave(run, name: str) -> Optional[float]:
    """Summed duration of the batch-level span ``name``, a node and wave."""
    spans = window_spans(run, lambda n: n == name)
    return ms_per_node_and_wave(
        run, sum(duration_ms(s) for s in spans), spans)


def self_ms(span: dict, children: List[dict]) -> float:
    """The span's duration less what its children cover of it."""
    lo, hi = span["t0_ns"], span["t1_ns"]
    covered = trace_reduce.union(trace_reduce.clip(
        ((c["t0_ns"], c["t1_ns"]) for c in children), lo, hi))
    return (hi - lo - trace_reduce.total_ns(covered)) / 1e6


def histogram_mean_ms(run, name: str) -> Optional[float]:
    """Mean observation of the histogram ``name`` over the window, in ms:
    sum and count at the window's end less those at its start, all nodes."""
    total = count = 0.0
    for nid, snap in run.metrics_end.items():
        end = snap["histograms"].get(name, {})
        start = run.metrics_start.get(nid, {}).get(
            "histograms", {}).get(name, {})
        total += (end.get("sum") or 0.0) - (start.get("sum") or 0.0)
        count += (end.get("count") or 0) - (start.get("count") or 0)
    return total / count * 1e3 if count else None


def unnamed_idle_pct(run) -> Optional[float]:
    """Of the traced wave's time with no device running anything, the
    share during which no work span of the program was open on any
    thread: idle time the program's own trace cannot explain."""
    if run.trace is None:
        return None
    lo, hi = run.traced_lo_ns, run.traced_hi_ns
    offset = trace_reduce.clock_offset_ns(run.trace)
    busy = trace_reduce.union(
        iv for p in trace_reduce.device_planes(run.trace)
        for iv in trace_reduce.busy(p, lo, hi))
    idle = [(a + offset, b + offset)
            for a, b in trace_reduce.gaps(busy, lo, hi)]
    idle_ns = trace_reduce.total_ns(idle)
    if idle_ns <= 0:
        return None
    work = trace_reduce.union(
        (s["t0_ns"], s["t1_ns"]) for s in run.spans
        if s["name"].startswith(WORK_PREFIXES) and s.get("t1_ns") is not None)
    named_ns = sum(trace_reduce.total_ns(trace_reduce.clip(work, a, b))
                   for a, b in idle)
    return (1.0 - named_ns / idle_ns) * 100.0
