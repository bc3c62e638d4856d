"""What a fabric does per message must not depend on its history
(ISSUE 26): the idempotent-enqueue duplicate window, shared by the
loopback fabric and the TCP broker, and the loopback fabric's
subscription index. Semantics are held with an injected clock; cost is
held by counting what a call visits, never by a timer.
"""
import random
import time

import pytest

from mpcium_tpu.transport import loopback
from mpcium_tpu.transport.dedup import WINDOW_S, DedupWindow
from mpcium_tpu.transport.loopback import LoopbackFabric, topic_matches
from mpcium_tpu.transport.tcp import BrokerServer, tcp_transport

KINDS = ("pubsub", "direct", "queue")


class _Clock:
    def __init__(self):
        self.now = 1000.0

    def __call__(self):
        return self.now


class _LoopbackQueue:
    """Idempotent enqueues into a loopback fabric under a stepped clock."""

    def __init__(self, clock):
        self.fabric = LoopbackFabric(workers=2)
        self.window = self.fabric._dedup = DedupWindow(clock=clock)
        self.got = []
        self.queues = self.fabric.transport().queues
        self.queues.dequeue("q.*", self.got.append)

    def enqueue(self, topic, data, idempotency_key=""):
        self.queues.enqueue(topic, data, idempotency_key=idempotency_key)

    def settle(self, n):
        self.fabric.drain(10)

    def close(self):
        self.fabric.close()


class _BrokerQueue:
    """The same through a TCP broker: the window is the broker's."""

    def __init__(self, clock):
        self.broker = BrokerServer(port=0)
        self.window = self.broker._dedup = DedupWindow(clock=clock)
        self.got = []
        self.transport = tcp_transport(self.broker.host, self.broker.port)
        self.transport.queues.dequeue("q.*", self.got.append)
        self._barrier()  # the broker has the subscription

    def _barrier(self):
        # frames of one connection are handled in order: when the reply
        # is here, the broker has dealt with every frame before it
        self.transport.client.kv_request({"op": "kvget", "k": "barrier"})

    def enqueue(self, topic, data, idempotency_key=""):
        self.transport.queues.enqueue(
            topic, data, idempotency_key=idempotency_key)
        self._barrier()  # its verdict falls before the test's next clock step

    def settle(self, n):
        deadline = time.monotonic() + 10
        while len(self.got) < n and time.monotonic() < deadline:
            time.sleep(0.005)

    def close(self):
        self.transport.client.close()
        self.broker.close()


@pytest.fixture(params=[_LoopbackQueue, _BrokerQueue],
                ids=["loopback", "broker"])
def queue(request):
    clock = _Clock()
    q = request.param(clock)
    q.clock = clock
    yield q
    q.close()


# -- (a) the duplicate window ------------------------------------------------


def test_duplicate_inside_the_window_is_dropped_and_not_refreshed(queue):
    queue.enqueue("q.r.1", b"first", idempotency_key="k")
    queue.clock.now += WINDOW_S - 1
    queue.enqueue("q.r.1", b"dup", idempotency_key="k")
    queue.settle(1)
    assert queue.got == [b"first"] and queue.window.hits == 1
    # had the duplicate refreshed the entry, this one would be dropped too
    queue.clock.now += 2
    queue.enqueue("q.r.1", b"after", idempotency_key="k")
    queue.settle(2)
    assert queue.got == [b"first", b"after"] and queue.window.hits == 1


def test_same_key_passes_once_the_window_has_passed(queue):
    queue.enqueue("q.r.1", b"one", idempotency_key="k")
    queue.clock.now += WINDOW_S
    queue.enqueue("q.r.1", b"two", idempotency_key="k")
    queue.clock.now += 1
    queue.enqueue("q.r.1", b"two-dup", idempotency_key="k")
    queue.settle(2)
    assert queue.got == [b"one", b"two"] and queue.window.hits == 1


def test_scope_is_the_topic_less_its_last_segment_and_the_key(queue):
    queue.enqueue("q.r.1", b"a", idempotency_key="k")
    queue.enqueue("q.r.2", b"same-scope", idempotency_key="k")
    queue.enqueue("q.s.1", b"other-topic", idempotency_key="k")
    queue.enqueue("q.r.1", b"other-key", idempotency_key="k2")
    queue.enqueue("q.r.1", b"no-key")
    queue.enqueue("q.r.1", b"no-key")
    queue.settle(5)
    assert sorted(queue.got) == sorted(
        [b"a", b"other-topic", b"other-key", b"no-key", b"no-key"])
    assert queue.window.hits == 1 and len(queue.window) == 3


def test_the_map_is_empty_after_its_keys_and_a_step_past_the_window(queue):
    for i in range(300):
        queue.clock.now += 0.01
        queue.enqueue(f"q.r.{i}", b"m", idempotency_key=f"k{i}")
    queue.settle(300)
    assert len(queue.window) == 300
    queue.clock.now += WINDOW_S
    queue.enqueue("q.r.x", b"m", idempotency_key="fresh")
    queue.settle(301)
    assert len(queue.window) == 1 and len(queue.got) == 301


def test_a_key_rewritten_in_place_still_expires():
    """The broker marks keys it did not admit itself (journal replay,
    replication); one it already held must not pin the head of the map."""
    clock = _Clock()
    window = DedupWindow(clock=clock)
    assert window.admit("q.r.1", "old")
    assert window.admit("q.r.1", "mid")
    clock.now += 100
    window.mark("q.r.1", "old")  # now the youngest
    clock.now += 30  # "mid" is 130 s old, "old" 30 s
    assert not window.admit("q.r.1", "old")
    assert len(window) == 1
    clock.now += WINDOW_S
    assert window.admit("q.r.1", "mid") and window.admit("q.r.1", "old")
    assert len(window) == 2 and window.hits == 1


def test_the_broker_marks_what_its_journal_replays(tmp_path):
    journal = str(tmp_path / "broker-queue.jsonl")
    first = BrokerServer(port=0, journal_path=journal)
    t = tcp_transport(first.host, first.port)
    t.queues.enqueue("q.r.1", b"pending", idempotency_key="k")
    t.client.kv_request({"op": "kvget", "k": "barrier"})
    t.client.close()
    first.close()
    second = BrokerServer(port=0, journal_path=journal)
    try:
        assert len(second._dedup) == 1
        assert not second._dedup.admit("q.r.2", "k")
    finally:
        second.close()


# -- (b) matching ------------------------------------------------------------


def _patterns_and_topics(rng):
    segs = ["a", "b", "ab", "mpc", "_inbox"]
    topics = {".".join(rng.choice(segs) for _ in range(rng.randint(1, 3)))
              for _ in range(150)}
    topics |= {t + ".*" for t in rng.sample(sorted(topics), 10)}  # literal *
    topics = sorted(topics)
    patterns = []
    for _ in range(300):
        t = rng.choice(topics)
        patterns.append(rng.choice([
            t,                                   # exact (some end in *)
            t.rsplit(".", 1)[0] + ".*",          # trailing * segment
            t[:rng.randint(1, len(t))] + "*",    # * after a partial segment
            "*",
        ]))
    return patterns, topics


@pytest.mark.parametrize("kind", KINDS)
def test_indexed_targets_equal_a_brute_force_scan(kind):
    rng = random.Random(26)
    patterns, topics = _patterns_and_topics(rng)
    fabric = LoopbackFabric(workers=2)
    try:
        subs = [fabric.subscribe(p, lambda _d: None, kind=kind)
                for p in patterns]
        for s in rng.sample(subs, 100):
            s.unsubscribe()
        matched = 0
        for topic in topics:
            want = [s for s in subs
                    if s.active and topic_matches(s.pattern, topic)]
            got = fabric._subs[kind].targets(topic)
            assert len(got) == len(want) and set(got) == set(want), topic
            assert got == fabric._subs[kind].targets(topic)  # one order
            matched += len(want)
        assert matched > len(topics)  # the case is not vacuous
        assert all(not fabric._subs[other].targets(t)
                   for other in KINDS if other != kind for t in topics)
    finally:
        fabric.close()


def test_deliveries_follow_the_index_for_each_kind():
    fabric = LoopbackFabric(workers=4)
    t = fabric.transport()
    got = []
    try:
        t.pubsub.subscribe("p.x", lambda d: got.append(("exact", d)))
        t.pubsub.subscribe("p.*", lambda d: got.append(("wild", d)))
        t.pubsub.subscribe("p.x*", lambda d: got.append(("partial", d)))
        t.pubsub.subscribe("p.y", lambda d: got.append(("other", d)))
        t.pubsub.publish("p.x", b"1")
        t.pubsub.publish("p.xyz", b"2")
        fabric.drain(10)
        assert sorted(got) == [("exact", b"1"), ("partial", b"1"),
                               ("partial", b"2"), ("wild", b"1"),
                               ("wild", b"2")]
        del got[:]
        t.direct.listen("d.*", lambda d: got.append(("direct", d)))
        t.direct.send("d.node0", b"3")
        assert got == [("direct", b"3")]
        del got[:]
        # the work queue still deals matching consumers in turn
        t.queues.dequeue("q.r.*", lambda d: got.append(("c1", d)))
        t.queues.dequeue("q.r.7", lambda d: got.append(("c2", d)))
        for i in range(4):
            t.queues.enqueue("q.r.7", bytes([i]))
        fabric.drain(10)
        assert sorted(c for c, _d in got) == ["c1", "c1", "c2", "c2"]
    finally:
        fabric.close()


# -- (c) no growth, and what a call visits -----------------------------------


class _Visits(dict):
    """A dict that counts the entries read one at a time by key and
    refuses a walk over all of them."""

    reads = 0

    def __getitem__(self, key):
        self.reads += 1
        return dict.__getitem__(self, key)

    def _walk(self, *a, **kw):
        raise AssertionError("a call walked the whole map")

    items = keys = values = _walk


def test_nothing_is_left_behind_by_rounds_of_subscribe_and_enqueue():
    clock = _Clock()
    fabric = LoopbackFabric(workers=4)
    fabric._dedup = DedupWindow(clock=clock)
    t = fabric.transport()
    got = []
    try:
        t.queues.dequeue("q.result.*", lambda _d: None)
        t.pubsub.subscribe("mpc:sign", lambda _d: None)
        before = fabric.stats()["gauges"]["transport.subscriptions"]
        assert before == 2
        for i in range(5000):
            sub = t.pubsub.subscribe(f"_inbox.{i}", got.append)
            t.pubsub.publish(f"_inbox.{i}", b"OK")
            t.queues.enqueue(f"q.result.{i}", b"r", idempotency_key=str(i))
            t.queues.enqueue(f"q.result.{i}", b"r", idempotency_key=str(i))
            if i % 500 == 0:
                fabric.drain(30)
            sub.unsubscribe()
        fabric.drain(30)
        stats = fabric.stats()
        assert stats["gauges"]["transport.subscriptions"] == before
        assert stats["gauges"]["transport.dedup_keys"] == 5000
        assert stats["counters"]["transport.dedup_hits"] == 5000
        assert not fabric._subs["pubsub"].exact.keys() - {"mpc:sign"}
        clock.now += WINDOW_S
        t.queues.enqueue("q.result.x", b"r", idempotency_key="x")
        assert fabric.stats()["gauges"]["transport.dedup_keys"] == 1
    finally:
        fabric.close()


def test_a_publish_looks_at_no_pattern_that_is_gone(monkeypatch):
    fabric = LoopbackFabric(workers=2)
    t = fabric.transport()
    try:
        t.pubsub.subscribe("mpc:sign", lambda _d: None)
        t.pubsub.subscribe("mpc.results.*", lambda _d: None)
        t.pubsub.subscribe("_inbox.live", lambda _d: None)
        for i in range(1000):
            t.pubsub.subscribe(f"_inbox.{i}", lambda _d: None).unsubscribe()
            t.pubsub.subscribe(f"_gone.{i}.*", lambda _d: None).unsubscribe()
        index = fabric._subs["pubsub"]
        assert len(index) == 3
        visited = []

        def counting(pattern, topic):
            visited.append(pattern)
            return topic_matches(pattern, topic)

        monkeypatch.setattr(loopback, "topic_matches", counting)
        index.exact = _Visits(index.exact)  # a walk of it would raise
        assert [s.pattern for s in index.targets("_inbox.live")] == [
            "_inbox.live"]
        assert index.targets("_inbox.7") == []
        # each publish tried the one live wildcard and nothing else
        assert visited == ["mpc.results.*"] * 2
    finally:
        fabric.close()


def test_an_enqueue_visits_only_the_expired_head_of_the_window():
    clock = _Clock()
    window = DedupWindow(clock=clock)
    window._seen = _Visits()
    for i in range(10):
        assert window.admit("q.r.1", f"early{i}")
    clock.now += 50
    for i in range(1000):
        assert window.admit("q.r.1", f"late{i}")
    window._seen.reads = 0
    clock.now += WINDOW_S - 50  # the ten are due, the thousand are not
    assert window.admit("q.r.1", "new")
    # ten expired heads and the first live one; the rest were not looked at
    assert window._seen.reads == 11 and len(window) == 1001
    window._seen.reads = 0
    assert not window.admit("q.r.1", "late500")
    assert window._seen.reads == 1  # only the live head
