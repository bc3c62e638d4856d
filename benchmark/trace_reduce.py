"""From the profiler's trace to numbers: device busy time, idle share,
kernel time, the costliest device operations and the longest idle gaps
named by what the host was doing. The benchmark owns this reduction so
that every PR computes the same numbers the same way.

A trace is held as plain data (so a small recorded one can sit beside
this file as JSON and be reduced without JAX or a chip):

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

Clocks. Device and host events of one trace share the profiler's clock.
The program's spans and the benchmark's phases are on ``time.monotonic_ns``.
The benchmark ties the two together itself: it emits
``jax.profiler.TraceAnnotation("bench_clock:<monotonic_ns>")`` events, each
named with the host reading taken as it was entered, so
``offset = reading - event.start`` maps profiler time onto monotonic time.
"""
from __future__ import annotations

import glob
import os
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

CLOCK_PREFIX = "bench_clock:"
DEVICE_PLANE_PREFIX = "/device:TPU:"
# the lines of a device plane that hold executed work, most detailed first
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"

Interval = Tuple[int, int]  # [start_ns, end_ns)


# -- reading ------------------------------------------------------------------

def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> dict:
    """The device planes' work lines and the host's ``bench_*``
    annotations, as plain data. Everything else in the file (python
    frames, thread-pool bookkeeping) is left behind."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        is_device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        lines = []
        for line in plane.lines:
            if is_device:
                if line.name not in (OP_LINE, MODULE_LINE):
                    continue
                events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                          for e in line.events]
            else:
                events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                          for e in line.events if e.name.startswith("bench_")]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# -- clocks -------------------------------------------------------------------

def clock_offset_ns(trace: dict) -> int:
    """monotonic_ns - profiler_ns, the median over the clock annotations."""
    offsets = []
    for plane in trace["planes"]:
        if plane["name"].startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane["lines"]:
            for name, start, _dur in line["events"]:
                if name.startswith(CLOCK_PREFIX):
                    offsets.append(int(name[len(CLOCK_PREFIX):]) - start)
    if not offsets:
        raise ValueError("the trace holds no bench_clock annotation")
    return int(statistics.median(offsets))


# -- intervals ----------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total_ns(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The complement of a sorted disjoint ``busy`` within [lo, hi)."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


# -- the device ---------------------------------------------------------------

def device_planes(trace: dict) -> List[dict]:
    return sorted((p for p in trace["planes"]
                   if p["name"].startswith(DEVICE_PLANE_PREFIX)),
                  key=lambda p: p["name"])


def _line(plane: dict, name: str) -> Optional[dict]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line
    return None


def work_events(plane: dict) -> List[list]:
    """The plane's executed-work events: its operations where the trace
    has them, else its whole programs."""
    line = _line(plane, OP_LINE) or _line(plane, MODULE_LINE)
    return line["events"] if line else []


def busy(plane: dict, lo: int, hi: int) -> List[Interval]:
    """Union of the intervals in which something ran on this device,
    clipped to [lo, hi), on the profiler's clock."""
    return union(clip(((s, s + d) for _n, s, d in work_events(plane)),
                      lo, hi))


def busy_and_idle(trace: dict, lo: int, hi: int) -> dict:
    """Per device and averaged: busy seconds and idle share of [lo, hi)."""
    planes = device_planes(trace)
    if not planes:
        raise ValueError("the trace holds no device plane")
    window_s = (hi - lo) / 1e9
    per = [total_ns(busy(p, lo, hi)) / 1e9 for p in planes]
    busy_s = sum(per) / len(per)
    return {"window_s": window_s, "busy_s": busy_s,
            "busy_s_per_device": per,
            "idle_share_pct": (1.0 - busy_s / window_s) * 100.0}


def program_name(event_name: str) -> str:
    """``jit_nonce_commitments(1234567)`` -> ``jit_nonce_commitments``."""
    return event_name.split("(", 1)[0]


def program_seconds(trace: dict, lo: int, hi: int) -> Dict[str, float]:
    """Device seconds of each compiled program (the ``XLA Modules`` line),
    summed over the events that start in [lo, hi) and averaged over the
    devices: a program partitioned over four chips runs on all of them at
    once and is counted once."""
    planes = device_planes(trace)
    out: Dict[str, float] = {}
    for plane in planes:
        line = _line(plane, MODULE_LINE)
        for name, start, dur in (line["events"] if line else []):
            if lo <= start < hi:
                key = program_name(name)
                out[key] = out.get(key, 0.0) + dur / 1e9 / len(planes)
    return out


def top_device_ops(trace: dict, lo: int, hi: int, n: int = 10) -> List[list]:
    """The operations (or programs, where the trace has no operations)
    that took most device time in [lo, hi), under the trace's names,
    summed over the devices. Control-flow shells (``while``,
    ``conditional``) cover their bodies' time again and are left out."""
    sums: Dict[str, float] = {}
    for plane in device_planes(trace):
        for name, start, dur in work_events(plane):
            # an operation's HLO text, or a program with its fingerprint
            name = program_name(name.split(" = ", 1)[0])
            if lo <= start < hi and not _is_shell(name):
                sums[name] = sums.get(name, 0.0) + dur / 1e9
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in ranked]


def _is_shell(op_name: str) -> bool:
    base = op_name.lstrip("%").split(".", 1)[0].split(" ", 1)[0]
    return base in ("while", "conditional", "call")


# -- gaps, named by what the host was doing -----------------------------------

def idle_gaps(trace: dict, lo: int, hi: int, host_spans: Sequence[dict],
              n: int = 10) -> List[list]:
    """The longest intervals of [lo, hi) in which NO device of the cell
    ran anything, each named by the host span that covers most of it.
    ``host_spans``: {"name", "t0_ns", "t1_ns"} on the monotonic clock
    (program spans and benchmark phases); the innermost (shortest) span
    wins among those that overlap a gap equally. Gaps are summed by name."""
    offset = clock_offset_ns(trace)
    all_busy = union(iv for p in device_planes(trace)
                     for iv in busy(p, lo, hi))
    sums: Dict[str, float] = {}
    for a, b in gaps(all_busy, lo, hi):
        name = _covering_span(a + offset, b + offset, host_spans)
        sums[name] = sums.get(name, 0.0) + (b - a) / 1e9
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in ranked]


def _covering_span(lo: int, hi: int, host_spans: Sequence[dict]) -> str:
    best, best_key = "unattributed", (0, 0)
    for s in host_spans:
        overlap = min(hi, s["t1_ns"]) - max(lo, s["t0_ns"])
        if overlap <= 0:
            continue
        key = (overlap, -(s["t1_ns"] - s["t0_ns"]))
        if key > best_key:
            best, best_key = s["name"], key
    return best
