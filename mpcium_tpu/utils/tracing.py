"""mpctrace core: a zero-dependency span model for cross-node tracing.

Span identity is ``trace_id`` / ``span_id`` / ``parent_id``; clocks are
``time.monotonic_ns`` so spans from every node of an in-process cluster
share one timebase and survive wall-clock steps. Attributes are public
metadata ONLY: attribute names are screened against the mpclint secret
taxonomy at record time and refused (value replaced, never logged)
unless the name was explicitly declassified via ``declassify_attr`` —
the runtime twin of the ``# mpcflow: declassified`` registry.

The module-level ``_ENABLED`` flag is the no-op fast path: with tracing
disabled (the default — the flagship bench number is measured this way)
``span()`` returns a shared inert singleton, ``emit()`` returns before
building anything, and engine phase timers skip their device syncs, so
transcripts are bit-identical and overhead is a single attribute load.

Sinks receive finished spans as plain dicts (see ``_span_dict``); the
flight recorder in ``mpcium_tpu.trace`` installs itself as the sink via
``enable(sink=...)``. This module deliberately imports nothing from the
rest of the project so every layer (wire, engines, scheduler, logging)
can depend on it without cycles.

Determinism note (MPL2xx): ids come from a process-local counter and a
keyed hash of public names — no ambient entropy, no wall clock — so a
traced protocol run makes exactly the same decisions as an untraced one.
"""
from __future__ import annotations

import functools
import hashlib
import itertools
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

now_ns = time.monotonic_ns
# the calling thread's CPU clock: what it ran, not what it waited. The
# interval between two readings is a span's ``cpu_s`` (opt-in: batch-level
# spans and ``client:submit``; OBSERVABILITY.md, "The interpreter account")
thread_cpu_ns = time.thread_time_ns

# -- the no-op fast path gate -------------------------------------------------
_ENABLED = False
_sink: Optional[Callable[[dict], None]] = None
_incident_hook: Optional[Callable[[str, str, dict], None]] = None
_clock_anchor_hook: Optional[Callable[[int], None]] = None

_ids = itertools.count(1)
_state = threading.local()  # .stack: List[Span] of open spans in this thread

# attribute names that hit the secret taxonomy but were reviewed as
# public metadata; name -> reason (the declassify registry, runtime half)
_DECLASSIFIED_ATTRS: Dict[str, str] = {}

_ATTR_SCALARS = (str, int, float, bool, type(None))


def enabled() -> bool:
    return _ENABLED


def enable(sink: Optional[Callable[[dict], None]] = None) -> None:
    """Turn tracing on. ``sink`` is called with each finished span dict;
    without one, spans only feed context propagation (log correlation,
    wire context) and are otherwise discarded."""
    global _ENABLED, _sink
    _sink = sink
    _ENABLED = True


def disable() -> None:
    global _ENABLED, _sink, _incident_hook, _clock_anchor_hook
    _ENABLED = False
    _sink = None
    _incident_hook = None
    _clock_anchor_hook = None


def set_incident_hook(hook: Optional[Callable[[str, str, dict], None]]) -> None:
    """Install the incident callback: ``hook(kind, node, attrs)``. The
    flight recorder uses it to dump buffers on shed/timeout/failure."""
    global _incident_hook
    _incident_hook = hook


def set_clock_anchor_hook(hook: Optional[Callable[[int], None]]) -> None:
    """Install the clock-anchor callback: ``hook(monotonic_ns)``. The
    flight recorder's ``arm()`` installs one that writes the reading
    into a running profiler capture, so this module stays jax-free."""
    global _clock_anchor_hook
    _clock_anchor_hook = hook


def clock_anchor() -> None:
    """Hand the span clock's current reading to the anchor hook: a
    profiler capture that holds it can be laid over a span export."""
    if not _ENABLED:
        return
    hook = _clock_anchor_hook
    if hook is not None:
        hook(now_ns())


def declassify_attr(name: str, reason: str) -> None:
    """Register a taxonomy-hitting attribute name as reviewed-public.
    The reason is mandatory and kept for the audit surface."""
    if not reason or not reason.strip():
        raise ValueError(f"declassify_attr({name!r}) requires a reason")
    _DECLASSIFIED_ATTRS[name] = reason


def declassified_attrs() -> Dict[str, str]:
    return dict(_DECLASSIFIED_ATTRS)


@functools.lru_cache(maxsize=1024)
def _is_secret_attr(name: str) -> bool:
    # attribute names are the code's own few: the taxonomy's verdict on a
    # name is looked up once, not at every span (2.5 us an attribute).
    # lazy import: taxonomy is stdlib-only but lives in the analysis
    # package; importing it here at module load would couple every
    # tracing user to the analyzer package's import time
    from ..analysis.taxonomy import is_secret_name

    return is_secret_name(name)


def clean_attrs(attrs: Dict[str, Any]) -> Dict[str, Any]:
    """Attribute hygiene: secret-taxonomy names are refused (value
    replaced with a marker, the value itself never retained) unless
    declassified; non-scalar values are reduced to their type name so
    no object repr can smuggle key material into a trace."""
    out: Dict[str, Any] = {}
    for k, v in attrs.items():
        if k not in _DECLASSIFIED_ATTRS and _is_secret_attr(k):
            out[k] = "<refused:secret-name>"
            continue
        if isinstance(v, _ATTR_SCALARS):
            out[k] = v
        else:
            out[k] = f"<obj:{type(v).__name__}>"
    return out


def trace_id_for(name: str) -> str:
    """Deterministic trace id from a public name (session id, drill
    name): every node derives the same id for the same session without
    coordination, so merged views group correctly even for spans that
    never rode a wire envelope."""
    return hashlib.sha256(b"mpctrace|" + name.encode()).hexdigest()[:16]


def _next_span_id() -> str:
    return f"{next(_ids):016x}"


def _stack() -> List["Span"]:
    st = getattr(_state, "stack", None)
    if st is None:
        st = []
        _state.stack = st
    return st


class Span:
    """An open span. Finish with ``end()`` or use ``span()`` as a
    context manager. Not thread-safe; a span belongs to one thread."""

    __slots__ = (
        "name", "trace_id", "span_id", "parent_id",
        "node", "tid", "t0_ns", "t1_ns", "kind", "attrs", "_pushed",
        "_cpu0_ns",
    )

    def __init__(
        self,
        name: str,
        *,
        trace_id: Optional[str] = None,
        parent_id: Optional[str] = None,
        node: str = "local",
        tid: str = "main",
        kind: str = "X",
        attrs: Optional[Dict[str, Any]] = None,
        cpu: bool = False,
    ) -> None:
        st = _stack()
        top = st[-1] if st else None
        self.name = name
        self.trace_id = trace_id or (top.trace_id if top else trace_id_for(name))
        self.parent_id = parent_id if parent_id is not None else (
            top.span_id if top else None
        )
        self.span_id = _next_span_id()
        # "local"/"main" are the unset sentinels: inherit from the
        # enclosing span so nested spans land on the right track
        self.node = top.node if (node == "local" and top is not None) else node
        self.tid = top.tid if (tid == "main" and top is not None) else tid
        self.t0_ns = now_ns()
        self.t1_ns = 0
        self.kind = kind
        self.attrs = clean_attrs(attrs) if attrs else {}
        self._pushed = False
        # cpu=True: the owning thread's CPU seconds between here and
        # end() become ``cpu_s`` (both ends must run on that thread)
        self._cpu0_ns = thread_cpu_ns() if cpu else None

    def set(self, **attrs: Any) -> None:
        self.attrs.update(clean_attrs(attrs))

    def end(self) -> None:
        if self._cpu0_ns is not None:
            self.attrs["cpu_s"] = (thread_cpu_ns() - self._cpu0_ns) / 1e9
        self.t1_ns = now_ns()
        sink = _sink
        if sink is not None:
            sink(_span_dict(self))

    def __enter__(self) -> "Span":
        _stack().append(self)
        self._pushed = True
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._pushed:
            st = _stack()
            if st and st[-1] is self:
                st.pop()
            elif self in st:  # defensive: unbalanced exit
                st.remove(self)
            self._pushed = False
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        self.end()


class _NoopSpan:
    """Shared inert span for the disabled fast path."""

    __slots__ = ()
    trace_id = None
    span_id = None
    parent_id = None

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        return None

    def set(self, **attrs: Any) -> None:
        return None

    def end(self) -> None:
        return None


NOOP_SPAN = _NoopSpan()


_SPAN_KW = ("trace_id", "parent_id", "node", "tid", "kind", "attrs", "cpu")


def span(name: str, **kw: Any):
    """Open a span (context manager). Known keywords (``trace_id``,
    ``parent_id``, ``node``, ``tid``, ``kind``, ``attrs``, ``cpu``) configure
    the span; anything else becomes an attribute. No-op singleton when
    disabled — the fast path is this one flag check."""
    if not _ENABLED:
        return NOOP_SPAN
    cfg = {k: kw.pop(k) for k in _SPAN_KW if k in kw}
    if kw:
        cfg["attrs"] = {**kw, **(cfg.get("attrs") or {})}
    return Span(name, **cfg)


def _span_dict(s: Span) -> dict:
    return {
        "name": s.name,
        "trace_id": s.trace_id,
        "span_id": s.span_id,
        "parent_id": s.parent_id,
        "node": s.node,
        "tid": s.tid,
        "t0_ns": s.t0_ns,
        "t1_ns": s.t1_ns,
        "kind": s.kind,
        "attrs": s.attrs,
    }


def emit(
    name: str,
    t0_ns: int,
    t1_ns: int,
    *,
    node: str = "local",
    tid: str = "main",
    trace_id: Optional[str] = None,
    parent_id: Optional[str] = None,
    kind: str = "X",
    **attrs: Any,
) -> Optional[str]:
    """Record an already-finished interval as a span (retroactive form:
    the scheduler turns queue-entry lifetimes into spans at dispatch or
    shed time without holding live span objects in its entries).
    Returns the span's id, for a caller that parents later spans under
    it; None when nothing was recorded."""
    if not _ENABLED:
        return None
    sink = _sink
    if sink is None:
        return None
    span_id = _next_span_id()
    sink({
        "name": name,
        "trace_id": trace_id or trace_id_for(name),
        "span_id": span_id,
        "parent_id": parent_id,
        "node": node,
        "tid": tid,
        "t0_ns": int(t0_ns),
        "t1_ns": int(t1_ns),
        "kind": kind,
        "attrs": clean_attrs(attrs) if attrs else {},
    })
    return span_id


def instant(name: str, *, node: str = "local", tid: str = "main",
            trace_id: Optional[str] = None, **attrs: Any) -> None:
    """Zero-duration marker event."""
    if not _ENABLED:
        return
    t = now_ns()
    emit(name, t, t, node=node, tid=tid, trace_id=trace_id, kind="i", **attrs)


def incident(kind: str, *, node: str = "local", tid: str = "main",
             **attrs: Any) -> None:
    """Mark an operational incident (shed, timeout, drill failure).
    Emits an instant span and fires the flight-recorder dump hook."""
    if not _ENABLED:
        return
    instant(f"incident:{kind}", node=node, tid=tid, **attrs)
    hook = _incident_hook
    if hook is not None:
        hook(kind, node, clean_attrs(attrs) if attrs else {})


def current_ids() -> Optional[Tuple[str, str]]:
    """(trace_id, span_id) of the innermost open span in this thread,
    or None. Used by utils.log for log/trace correlation."""
    if not _ENABLED:
        return None
    st = getattr(_state, "stack", None)
    if not st:
        return None
    top = st[-1]
    return (top.trace_id, top.span_id)


def wire_context() -> Optional[Dict[str, str]]:
    """Trace context in wire form ({"t": trace_id, "s": span_id}) for
    the optional envelope field, or None when no span is open."""
    ids = current_ids()
    if ids is None:
        return None
    return {"t": ids[0], "s": ids[1]}


class PhaseTimer:
    """Engine-side phase instrumentation: device-phase spans with a sync
    at each phase boundary, ONLY when tracing is on (or a legacy
    ``phase_times`` dict was requested). ``sync`` is supplied by the
    engine (``jax.block_until_ready``) so this module stays jax-free.

    ``mark(name, *tensors)`` closes the interval since the previous mark
    as a span named ``phase:<name>``; with tracing disabled and no
    ``phase_times`` dict, ``mark`` is one attribute load and a return —
    no sync, no allocation — which is what keeps untraced transcripts
    bit-identical.
    """

    __slots__ = ("on", "phases", "_sync", "node", "tid", "trace_id",
                 "parent_id", "last_ns", "_last_span_id")

    def __init__(
        self,
        engine: str,
        sync: Callable[..., Any],
        *,
        phase_times: Optional[Dict[str, float]] = None,
        node: str = "local",
        tid: Optional[str] = None,
    ) -> None:
        self.on = _ENABLED or phase_times is not None
        self.phases = phase_times
        self._sync = sync
        self.node = node
        self.tid = tid or engine
        self.trace_id = trace_id_for(engine) if self.on else None
        ids = current_ids()
        self.parent_id = ids[1] if ids else None
        if ids:
            self.trace_id = ids[0]
        self.last_ns = now_ns() if self.on else 0
        self._last_span_id: Optional[str] = None

    def mark(self, name: str, *tensors: Any, **attrs: Any) -> None:
        if not self.on:
            return
        if tensors:
            self._sync(tensors)
        t = now_ns()
        if self.phases is not None:
            self.phases[name] = (t - self.last_ns) / 1e9
            # derived sub-phase scalars (the OT host/device split) keep
            # their legacy flat keys so old consumers read the same dict
            for k, v in attrs.items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    self.phases[f"{name}_{k}"] = v
        emit(
            f"phase:{name}", self.last_ns, t,
            node=self.node, tid=self.tid,
            trace_id=self.trace_id, parent_id=self.parent_id,
            **attrs,
        )
        self.last_ns = t


def phase_share(spans: List[dict]) -> Dict[str, float]:
    """Fold phase spans back into the bench-table shape: span
    ``phase:<name>`` -> ``{name: seconds}`` and pipeline host stages
    ``host:<name>`` -> ``{host_<name>: seconds}``, with numeric span
    attrs flattened as ``<name>_<attr>`` (the OT host/device split).
    This is how bench.py reproduces its phase-share fields from the
    trace instead of the old private dict; without the ``host:`` fold a
    cohorted run's wire stages would silently vanish from the table.

    A run that produced no phase spans (watchdog fallback, engine died
    before its first mark) returns the explicit ``{"no_spans": 0.0}``
    marker instead of an empty dict, so downstream merges keep their
    keys and a reader can tell "nothing measured" from "lost"."""
    out: Dict[str, float] = {}
    for s in spans:
        if s["name"].startswith("phase:"):
            name = s["name"][len("phase:"):]
        elif s["name"].startswith("host:"):
            name = "host_" + s["name"][len("host:"):]
        else:
            continue
        out[name] = out.get(name, 0.0) + (s["t1_ns"] - s["t0_ns"]) / 1e9
        for k, v in s.get("attrs", {}).items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[f"{name}_{k}"] = v
    if not out:
        return {"no_spans": 0.0}
    return out


def device_idle_fraction(spans: List[dict]) -> float:
    """Fraction of the traced window in which the device had NO
    ``phase:*`` span open — the idle metric ROADMAP item 4's zero-idle
    pipeline is judged by.

    The window spans from the first to the last edge over BOTH device
    (``phase:*``) and pipeline host-stage (``host:*``) spans, so host
    wire time at the edges counts against the device. Overlapping
    device spans (counter-phase cohorts) are unioned, not summed —
    overlap is exactly the effect being measured. Returns 0.0 when no
    device spans exist (nothing measured ⇒ nothing claimable)."""
    dev: List[tuple] = []
    lo = hi = None
    for s in spans:
        name = s.get("name", "")
        if not (name.startswith("phase:") or name.startswith("host:")):
            continue
        t0, t1 = s["t0_ns"], s["t1_ns"]
        lo = t0 if lo is None else min(lo, t0)
        hi = t1 if hi is None else max(hi, t1)
        if name.startswith("phase:"):
            dev.append((t0, t1))
    if not dev or hi is None or hi <= lo:
        return 0.0
    dev.sort()
    busy = 0
    cur0, cur1 = dev[0]
    for t0, t1 in dev[1:]:
        if t0 > cur1:
            busy += cur1 - cur0
            cur0, cur1 = t0, t1
        else:
            cur1 = max(cur1, t1)
    busy += cur1 - cur0
    return max(0.0, 1.0 - busy / (hi - lo))
