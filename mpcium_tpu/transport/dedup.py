"""The idempotent-enqueue duplicate window, shared by both fabrics
(JetStream's duplicate-window semantics behind Nats-Msg-Id,
message_queue.go:100-110): a key repeated within the window is dropped and
does not refresh its entry; the same key after the window passes, so a
legitimate later re-submission (a second reshare of one wallet) goes
through; the map holds no more than the window's keys.

Keys enter with a monotone clock and only at the tail, so the dict's
insertion order is age order: expiry pops from the head until the first
live entry, and a call never looks at the rest of the map. Not
thread-safe: the owning fabric calls it under its own lock.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Tuple

WINDOW_S = 120.0


class DedupWindow:
    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self._clock = clock
        self._seen: Dict[Tuple[str, str], float] = {}
        self.hits = 0  # duplicates suppressed (``transport.dedup_hits``)

    def __len__(self) -> int:
        return len(self._seen)

    def admit(self, topic: str, key: str) -> bool:
        """True for the first sight of ``key`` in its scope (the topic
        less its last segment) within the window, and the key is
        remembered; False for a duplicate, which leaves the entry as old
        as it was."""
        now = self._expire()
        scope = _scope(topic, key)
        seen_at = self._seen.get(scope)
        # the window decides, not mere presence: whatever order entries
        # arrived in, lazy expiry cannot change a verdict
        if seen_at is not None and now - seen_at < WINDOW_S:
            self.hits += 1
            return False
        self._remember(scope, now)
        return True

    def mark(self, topic: str, key: str) -> None:
        """Remember ``key`` as seen now without asking (the broker's
        journal replay and replication: messages it did not admit itself)."""
        self._remember(_scope(topic, key), self._expire())

    def _remember(self, scope: Tuple[str, str], now: float) -> None:
        self._seen.pop(scope, None)  # a re-marked key moves to the tail
        self._seen[scope] = now

    def _expire(self) -> float:
        now = self._clock()
        seen = self._seen
        while seen:
            oldest = next(iter(seen))
            if now - seen[oldest] < WINDOW_S:
                break
            del seen[oldest]
        return now


def _scope(topic: str, key: str) -> Tuple[str, str]:
    return (topic.rsplit(".", 1)[0], key)
