"""The interpreter account: what the process's threads did with the one
interpreter, by the kind of thread.

Every span and histogram of the program is wall time: a thread that
holds the interpreter, one that waits for it and one parked on a lock
all read the same. This module adds the other clock. A thread's ROLE is
the longest prefix of ``ROLES`` its name starts with; ``snapshot()``
walks the live threads, reads each one's CPU clock
(``pthread_getcpuclockid``; nothing is paid until a snapshot is asked
for) and sums by role, with what exited threads left behind;
``Canary`` is a thread that sleeps a fixed 10 ms and reports how much
later than that it woke: what a thread that lets go of the interpreter
waits to get it back. OBSERVABILITY.md ("The interpreter account")
says how to read them. Stdlib only, like ``tracing``.
"""
from __future__ import annotations

import functools
import threading
import time
import weakref
from typing import Callable, Dict, Tuple

# thread-name prefix -> role; the longest matching prefix wins. A thread
# the package starts whose name matches none is a test failure
# (tests/test_interp_account.py), not an ``other``.
ROLES = {
    "MainThread": "main",      # the caller of the client SDK; the daemon's boot
    "loopback-q": "loopback-q",  # the fabric's queue pool: bridges, result deliveries
    "loopback": "loopback",    # its pub/sub pool: _on_sign, manifests, envelopes
    "tcpbus-q": "tcpbus-q",    # the same two pools (and the reader) over TCP
    "tcpbus": "tcpbus",
    "broker": "broker",        # an in-process BrokerServer's threads
    "bsign": "bsign",          # a batch's thread: claims, share loads, the party
    "bdkg": "bsign",
    "brs": "bsign",
    "send": "send",            # a session's sender
    "keygen-wait": "keygen-wait",  # a per-wallet keygen's result waiter
    "registry": "registry",
    "session-gc": "session-gc",
    "batch-wheel": "batch-wheel",
    "health": "health",
    "pipe-host": "pipe-host",
    "ot-host": "ot-host",
    "interp": "interp",        # the canary below
    "timer": "timer",          # threading.Timer threads (hello deadline, fault delays)
    "soak": "soak",
    "chaos": "chaos",
}
OTHER = "other"
_BY_LENGTH = sorted(ROLES, key=len, reverse=True)

_lock = threading.Lock()
_retired_ns: Dict[str, int] = {}  # role -> CPU of its threads that are gone
# threads past retire(): counted in _retired_ns, no longer read
_retired: "weakref.WeakSet[threading.Thread]" = weakref.WeakSet()
_seen: Dict[threading.Thread, int] = {}  # live at the last snapshot -> its clock then


@functools.lru_cache(maxsize=8192)
def role_of(thread_name: str) -> str:
    for prefix in _BY_LENGTH:
        if thread_name.startswith(prefix):
            return ROLES[prefix]
    return OTHER


def retire() -> None:
    """The calling thread is about to exit: its CPU time joins its
    role's retired total, so a batch thread's work is not lost with it.
    Called as the last act of the program's short-lived threads. While
    a snapshot walks the threads this waits (a few ms): a thread that
    retires does not exit under the walk."""
    me = threading.current_thread()
    role = role_of(me.name)
    with _lock:
        if me not in _retired:
            _retired.add(me)
            _retired_ns[role] = _retired_ns.get(role, 0) + time.thread_time_ns()


def _cpu_ns(thread: threading.Thread):
    """``thread``'s CPU clock, or None where it cannot be read (it has
    just exited, or the platform has no such clock)."""
    if not thread.is_alive():
        return None
    try:
        return time.clock_gettime_ns(time.pthread_getcpuclockid(thread.ident))
    except (OSError, AttributeError, TypeError):
        return None


def snapshot() -> Dict[str, Dict[str, float]]:
    """``{"cpu_s": {role: seconds}, "threads": {role: live now}}``: each
    role's CPU seconds since the process began (live threads' clocks
    now, plus what retired and vanished threads had: monotone) and its
    count of live threads (0 for a role whose threads have all gone).
    ~1 us a thread."""
    global _seen
    threads: Dict[str, int] = {}
    cpu_ns: Dict[str, int] = {}
    live: Dict[threading.Thread, int] = {}
    with _lock:
        for t in threading.enumerate():
            role = role_of(t.name)
            threads[role] = threads.get(role, 0) + 1
            ns = None if t in _retired else _cpu_ns(t)
            if ns is not None:
                live[t] = ns
                cpu_ns[role] = cpu_ns.get(role, 0) + ns
        # a thread that went without retire() (a closed pool's worker, a
        # timer) leaves what it showed at the last snapshot
        for t, ns in _seen.items():
            if t not in live and t not in _retired:
                role = role_of(t.name)
                _retired_ns[role] = _retired_ns.get(role, 0) + ns
        _seen = live
        for role, ns in _retired_ns.items():
            cpu_ns[role] = cpu_ns.get(role, 0) + ns
    return {"cpu_s": {r: ns / 1e9 for r, ns in cpu_ns.items()},
            "threads": {r: float(threads.get(r, 0))
                        for r in {*cpu_ns, *threads}}}


# a registry asks for the gauges at every snapshot, and a caller may
# snapshot twice in a row for one counter each (the benchmark does, after
# a wave and before the next): a walk is ~6 us a thread where thread
# clocks are system calls, 15-25 ms for 2,200 threads
MIN_WALK_INTERVAL_S = 0.25
_last_gauges: Tuple[float, Dict[str, float]] = (float("-inf"), {})


def gauges() -> Dict[str, float]:
    """The snapshot as registry gauges: ``interp.cpu_s.<role>`` and
    ``interp.threads.<role>``. A walk younger than
    ``MIN_WALK_INTERVAL_S`` is served again."""
    global _last_gauges
    at, out = _last_gauges
    now = time.monotonic()
    if now - at < MIN_WALK_INTERVAL_S:
        return dict(out)
    snap = snapshot()
    out = {f"interp.cpu_s.{r}": v for r, v in snap["cpu_s"].items()}
    out.update((f"interp.threads.{r}", v) for r, v in snap["threads"].items())
    _last_gauges = (now, out)
    return dict(out)


class Canary:
    """The hand-over lag: a daemon thread ``interp-canary`` that sleeps
    ``PERIOD_S`` and hands ``observe`` the seconds by which it overslept
    (``monotonic``). Each wake-up is one acquisition of the interpreter,
    so idle this reads the timer's slack and beside busy threads what
    they make a thread wait (about ``sys.getswitchinterval()`` beside one
    that spins). One fixed period, no setting; ``close()`` ends it."""

    PERIOD_S = 0.010

    def __init__(self, observe: Callable[[float], None]) -> None:
        self._observe = observe
        self._stop = threading.Event()
        self.thread = threading.Thread(
            target=self._run, name="interp-canary", daemon=True)
        self.thread.start()

    def _run(self) -> None:
        period = self.PERIOD_S
        while not self._stop.is_set():
            t0 = time.monotonic()
            time.sleep(period)
            self._observe(max(0.0, time.monotonic() - t0 - period))

    def close(self) -> None:
        self._stop.set()
        if self.thread is not threading.current_thread():
            self.thread.join()
