"""node.daemon health publisher: payload shape, the Prometheus sidecar
key, periodic republish, and the warn-don't-crash contract when the KV
put raises (no broker, no cluster — stub consumer + in-memory KV)."""
import json
import threading
import time

from mpcium_tpu.node.daemon import health_loop, publish_health
from mpcium_tpu.store.kvstore import MemoryKV
from mpcium_tpu.utils.metrics import MetricsRegistry


class _StubConsumer:
    """The slice of EventConsumer the health beat reads."""

    def __init__(self):
        self.metrics = MetricsRegistry()
        self.metrics.counter("scheduler.batches_fired_total").inc(3)
        self.metrics.gauge("scheduler.queue_depth").set(2)

    def health(self):
        return {
            "sessions": 0,
            "batch_signing": True,
            "metrics": self.metrics.snapshot(),
        }


def test_publish_health_payload_and_prom_sidecar():
    kv = MemoryKV()
    consumer = _StubConsumer()
    snap = publish_health(consumer, kv, "node0")
    assert "ts" in snap and snap["batch_signing"] is True

    stored = json.loads(kv.get("health/node0"))
    assert stored["sessions"] == 0
    assert stored["metrics"]["counters"][
        "scheduler.batches_fired_total"] == 3.0
    assert stored["ts"] == snap["ts"]

    prom = kv.get("health/node0.prom").decode()
    assert "# TYPE scheduler_batches_fired_total counter" in prom
    assert 'scheduler_batches_fired_total{node="node0"} 3.0' in prom
    assert 'scheduler_queue_depth{node="node0"} 2.0' in prom


def test_health_loop_republishes_periodically():
    kv = MemoryKV()
    consumer = _StubConsumer()
    stop = threading.Event()
    seen = []
    orig_put = kv.put

    def counting_put(key, value):
        seen.append(key)
        return orig_put(key, value)

    kv.put = counting_put
    t = threading.Thread(
        target=health_loop, args=(consumer, kv, "node0", stop, 0.05),
        daemon=True,
    )
    t.start()
    deadline = time.monotonic() + 5.0
    while seen.count("health/node0") < 3 and time.monotonic() < deadline:
        time.sleep(0.02)
    stop.set()
    t.join(2.0)
    assert seen.count("health/node0") >= 3
    assert seen.count("health/node0.prom") >= 3


def test_health_payload_tracks_compile_warming_to_ready():
    """The compile-wall section rides the health beat: a daemon that is
    still tracing its first XLA compiles publishes state=warming, then
    flips to ready — visible to anything polling health/<node>."""
    from mpcium_tpu.perf import compile_watch

    class _CompileAwareConsumer(_StubConsumer):
        def health(self):
            compile_watch.export_gauges(self.metrics)
            h = super().health()
            h["compile"] = compile_watch.health_summary()
            return h

    compile_watch.reset()
    try:
        kv = MemoryKV()
        consumer = _CompileAwareConsumer()

        compile_watch.mark_warming()
        compile_watch.finish(compile_watch.begin("dkg.run", "B4|q3|ecdsa"))
        snap = publish_health(consumer, kv, "node0")
        assert snap["compile"]["state"] == "warming"
        assert snap["compile"]["compiles"] == 1
        stored = json.loads(kv.get("health/node0"))
        assert stored["compile"]["state"] == "warming"
        assert stored["metrics"]["gauges"]["compile.ready"] == 0.0

        compile_watch.mark_ready()
        snap = publish_health(consumer, kv, "node0")
        assert snap["compile"]["state"] == "ready"
        stored = json.loads(kv.get("health/node0"))
        assert stored["metrics"]["gauges"]["compile.ready"] == 1.0
        prom = kv.get("health/node0.prom").decode()
        assert 'compile_ready{node="node0"} 1.0' in prom
    finally:
        compile_watch.reset()


def test_node_health_carries_compile_and_no_claims():
    """A real EventConsumer's beat: the payload and the ``.prom`` sidecar
    carry the node's own compile state and nothing of a measurement
    ledger (a signing node publishes what it does, not what a ROADMAP
    owes)."""
    from types import SimpleNamespace

    from mpcium_tpu.consumers.event_consumer import EventConsumer
    from mpcium_tpu.perf import compile_watch
    from mpcium_tpu.transport.loopback import LoopbackFabric

    compile_watch.reset()
    fabric = LoopbackFabric()
    try:
        consumer = EventConsumer(
            SimpleNamespace(node_id="node0", session_wal=None),
            fabric.transport(),
        )
        kv = MemoryKV()
        snap = publish_health(consumer, kv, "node0")
        assert snap["node"] == "node0"
        assert snap["compile"]["state"] == "ready"
        assert "claims" not in snap
        stored = json.loads(kv.get("health/node0"))
        assert "compile" in stored and "claims" not in stored
        gauges = stored["metrics"]["gauges"]
        assert gauges["compile.ready"] == 1.0
        assert not [g for g in gauges if g.startswith("claims.")]
        prom = kv.get("health/node0.prom").decode()
        assert 'compile_ready{node="node0"} 1.0' in prom
        assert not [ln for ln in prom.splitlines()
                    if ln.startswith(("claims_", "# TYPE claims_"))]
    finally:
        fabric.close()
        compile_watch.reset()


def test_health_loop_survives_kv_put_raise():
    consumer = _StubConsumer()
    stop = threading.Event()
    calls = []

    class _BrokenKV:
        def put(self, key, value):
            calls.append(key)
            raise OSError("control plane down")

    t = threading.Thread(
        target=health_loop,
        args=(consumer, _BrokenKV(), "node0", stop, 0.05),
        daemon=True,
    )
    t.start()
    deadline = time.monotonic() + 5.0
    while len(calls) < 3 and time.monotonic() < deadline:
        time.sleep(0.02)
    stop.set()
    t.join(2.0)
    # the beat kept beating THROUGH the failures, and the thread exits
    # cleanly on stop rather than dying on the first raise
    assert len(calls) >= 3
    assert not t.is_alive()
