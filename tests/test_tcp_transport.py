"""TCP bus: same four delivery semantics as loopback, across sockets."""
import threading
import time

import pytest

from mpcium_tpu.transport.api import Permanent, QueueConfig, TransportError
from mpcium_tpu.transport.tcp import BrokerServer, TcpClient, tcp_transport


@pytest.fixture()
def broker():
    b = BrokerServer(port=0, queue_config=QueueConfig(max_deliver=3))
    yield b
    b.close()


def _wait(cond, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return False


def test_pubsub_fanout(broker):
    t1 = tcp_transport(broker.host, broker.port)
    t2 = tcp_transport(broker.host, broker.port)
    got = []
    t1.pubsub.subscribe("topic:x", lambda d: got.append(("t1", d)))
    t2.pubsub.subscribe("topic:x", lambda d: got.append(("t2", d)))
    time.sleep(0.05)  # sub registration in flight
    t1.pubsub.publish("topic:x", b"hello")
    assert _wait(lambda: len(got) == 2)
    assert sorted(got) == [("t1", b"hello"), ("t2", b"hello")]
    t1.client.close()
    t2.client.close()


def test_direct_ack_and_failure(broker):
    t1 = tcp_transport(broker.host, broker.port)
    t2 = tcp_transport(broker.host, broker.port)
    got = []
    t2.direct.listen("direct:n2", lambda d: got.append(d))
    time.sleep(0.05)
    t1.direct.send("direct:n2", b"ping")  # blocks until acked
    assert got == [b"ping"]
    with pytest.raises(TransportError):
        t1.client.direct_send("direct:nobody", b"x", timeout_s=0.05, attempts=2,
                              retry_delay_s=0.01)
    t1.client.close()
    t2.client.close()


def test_queue_semantics(broker):
    t = tcp_transport(broker.host, broker.port)
    dead = []
    t.set_dead_letter_handler(lambda topic, data, n: dead.append((topic, n)))
    attempts = []

    def failing(d):
        attempts.append(d)
        raise RuntimeError("boom")

    t.queues.dequeue("q.f.*", failing)
    time.sleep(0.05)
    t.queues.enqueue("q.f.1", b"m", idempotency_key="k1")
    t.queues.enqueue("q.f.1", b"m", idempotency_key="k1")  # dedup
    assert _wait(lambda: len(dead) == 1, timeout=10)
    assert len(attempts) == 3
    # durable buffering before consumer exists
    t.queues.enqueue("q.late.1", b"early")
    got = []
    t.queues.dequeue("q.late.*", lambda d: got.append(d))
    assert _wait(lambda: got == [b"early"])
    t.client.close()


def test_reply_wrapper(broker):
    t1 = tcp_transport(broker.host, broker.port)
    t2 = tcp_transport(broker.host, broker.port)
    import json

    seen = []
    t2.pubsub.subscribe("cmd", lambda d: seen.append(json.loads(d)))
    time.sleep(0.05)
    t1.pubsub.publish_with_reply("cmd", "inbox.1", b"\x01\x02")
    assert _wait(lambda: len(seen) == 1)
    assert seen[0]["reply"] == "inbox.1"
    assert bytes.fromhex(seen[0]["data"]) == b"\x01\x02"
    t1.client.close()
    t2.client.close()


@pytest.mark.slow  # a three-node cluster with ECDSA keygen: over a minute
def test_full_cluster_over_tcp(tmp_path):
    """A 3-node MPC cluster across the TCP bus: wallet + EdDSA sign."""
    from mpcium_tpu import wire
    from mpcium_tpu.cluster import LocalCluster, load_test_preparams
    from mpcium_tpu.core import hostmath as hm

    cluster = LocalCluster(
        n_nodes=3, threshold=1, root_dir=str(tmp_path),
        preparams=load_test_preparams(), transport="tcp",
    )
    try:
        ev = cluster.create_wallet_sync("tcp-wallet")
        tx = b"tcp tx"
        res = cluster.sign_sync(
            wire.SignTxMessage(
                key_type="ed25519", wallet_id="tcp-wallet",
                network_internal_code="sol", tx_id="tcp-tx-1", tx=tx,
            )
        )
        assert res.result_type == wire.RESULT_SUCCESS, res.error_reason
        assert hm.ed25519_verify(
            bytes.fromhex(ev.eddsa_pub_key), tx, bytes.fromhex(res.signature)
        )
    finally:
        cluster.close()


def test_broker_journal_survives_restart(tmp_path):
    """File-backed queue durability: a broker restart redelivers every
    enqueued-but-unacked message (reference JetStream WorkQueue file
    retention, message_queue.go:56-63)."""
    journal = str(tmp_path / "queue.jsonl")
    b1 = BrokerServer(port=0, journal_path=journal)
    t1 = tcp_transport(b1.host, b1.port)
    t1.queues.enqueue("mpc.results.a", b"payload-1", idempotency_key="k1")
    t1.queues.enqueue("mpc.results.b", b"payload-2")
    time.sleep(0.3)  # let the broker journal the enqueues
    t1.client.close()
    b1.close()  # broker dies with no consumer ever attached

    b2 = BrokerServer(port=0, journal_path=journal)
    t2 = tcp_transport(b2.host, b2.port)
    got = []
    evt = threading.Event()

    def handler(data):
        got.append(data)
        if len(got) == 2:
            evt.set()

    sub = t2.queues.dequeue("mpc.results.*", handler)
    assert evt.wait(10), f"redelivery after restart failed (got {got})"
    assert sorted(got) == [b"payload-1", b"payload-2"]
    # acked messages are NOT redelivered by the next restart
    time.sleep(0.3)
    sub.unsubscribe()
    t2.client.close()
    b2.close()
    b3 = BrokerServer(port=0, journal_path=journal)
    t3 = tcp_transport(b3.host, b3.port)
    got3 = []
    t3.queues.dequeue("mpc.results.*", got3.append)
    time.sleep(0.8)
    assert got3 == []
    t3.client.close()
    b3.close()


def test_broker_auth(tmp_path):
    """Token auth: unauthenticated or wrong-token clients are rejected
    (reference NATS credentials, main.go:346-359)."""
    b = BrokerServer(port=0, auth_token="s3cret-token")
    try:
        # correct token works end-to-end
        t_ok = tcp_transport(b.host, b.port, auth_token="s3cret-token")
        got = []
        evt = threading.Event()
        t_ok.pubsub.subscribe("x.y", lambda d: (got.append(d), evt.set()))
        time.sleep(0.2)
        t_ok.pubsub.publish("x.y", b"hello")
        assert evt.wait(5)

        # wrong token rejected at connect
        with pytest.raises(TransportError):
            tcp_transport(b.host, b.port, auth_token="wrong")

        # tokenless client: frames before auth are ignored/dropped
        t_no = tcp_transport(b.host, b.port)
        got2 = []
        t_no.pubsub.subscribe("x.y", got2.append)
        time.sleep(0.2)
        t_ok.pubsub.publish("x.y", b"again")
        time.sleep(0.5)
        assert got2 == [], "unauthenticated subscribe must not receive"
        t_no.client.close()
        t_ok.client.close()
    finally:
        b.close()


def test_encrypted_channel_roundtrip():
    """AEAD channel (X25519 + token-bound HKDF + ChaCha20-Poly1305):
    pub/sub, direct and queue traffic all work over encrypt=True, and the
    wire carries no plaintext frames."""
    import socket as _socket
    import threading as _threading

    b = BrokerServer(port=0, auth_token="chan-token", encrypt=True)
    try:
        t1 = tcp_transport(b.host, b.port, auth_token="chan-token",
                           encrypt=True)
        t2 = tcp_transport(b.host, b.port, auth_token="chan-token",
                           encrypt=True)
        got = []
        evt = _threading.Event()
        sub = t2.pubsub.subscribe(
            "enc.topic", lambda d: (got.append(d), evt.set())
        )
        time.sleep(0.1)  # sub registration in flight
        t1.pubsub.publish("enc.topic", b"secret-payload")
        assert evt.wait(5) and got == [b"secret-payload"]
        sub.unsubscribe()

        # raw socket peeking: past the plaintext hello, frames are
        # ciphertext (no JSON braces / payload bytes on the wire)
        s = _socket.create_connection((b.host, b.port), timeout=5)
        s.sendall(b'{"op":"ehello","epub":"' + b"00" * 32 + b'"}\n')
        line = b""
        s.settimeout(5)
        while b"\n" not in line:
            line += s.recv(4096)
        import json as _json

        hello = _json.loads(line.split(b"\n", 1)[0])
        assert hello["op"] == "ehello" and len(hello["epub"]) == 64
        s.close()
    finally:
        b.close()


def test_encrypted_channel_rejects_wrong_token():
    from mpcium_tpu.transport.api import TransportError

    b = BrokerServer(port=0, auth_token="right-token", encrypt=True)
    try:
        with pytest.raises(TransportError):
            TcpClient(b.host, b.port, auth_token="wrong-token", encrypt=True)
    finally:
        b.close()


def test_hashed_token_config():
    """The broker accepts a sha256:<hex> stored token; clients still
    present the plaintext."""
    import hashlib

    digest = "sha256:" + hashlib.sha256(b"pw12345").hexdigest()
    b = BrokerServer(port=0, auth_token=digest)
    try:
        t = tcp_transport(b.host, b.port, auth_token="pw12345")
        t.pubsub.publish("x", b"ok")  # connection is live and authed
    finally:
        b.close()


def test_queue_ttl_expires_orphaned_results():
    """A message on a per-tx result topic whose sole requester is gone
    must not pend forever: once past queue_ttl_s it takes the
    dead-letter path on the next dispatch attempt (triggered by any new
    subscription's pending flush) instead of accumulating in memory,
    the journal, and every standby."""
    b = BrokerServer(port=0, queue_ttl_s=0.3)
    try:
        t = tcp_transport(b.host, b.port)
        dead = []
        t.set_dead_letter_handler(
            lambda topic, data, n: dead.append((topic, data))
        )
        # no subscriber for this per-tx topic — the requester timed out
        # and unsubscribed before the node published the result
        t.queues.enqueue("q.result.tx-orphan", b"late-result")
        assert _wait(lambda: len(b._pending_q) == 1)
        time.sleep(0.4)  # let the TTL lapse
        # any unrelated subscription flushes pending through dispatch
        t.queues.dequeue("q.other.*", lambda d: None)
        assert _wait(lambda: ("q.result.tx-orphan", b"late-result") in dead)
        assert _wait(lambda: len(b._pending_q) == 0)
        assert not b._enq_ts
        # a live (young) message is NOT expired by the flush
        got = []
        t.queues.enqueue("q.result.tx-live", b"r2")
        t.queues.dequeue("q.result.tx-live", lambda d: got.append(d))
        assert _wait(lambda: got == [b"r2"])
        t.client.close()
    finally:
        b.close()


def test_queue_ttl_sweep_on_idle_broker():
    """The sweep thread must expire orphans even when NO new
    subscription ever triggers a pending flush (quiet broker)."""
    b = BrokerServer(port=0, queue_ttl_s=0.3)
    b_sweep_interval_floor = 1.0  # _ttl_sweep_loop clamps to >= 1 s
    try:
        t = tcp_transport(b.host, b.port)
        dead = []
        t.set_dead_letter_handler(
            lambda topic, data, n: dead.append((topic, data))
        )
        time.sleep(0.05)  # dead_sub registration in flight
        t.queues.enqueue("q.result.tx-idle", b"late")
        assert _wait(lambda: len(b._pending_q) == 1)
        # no dequeue() anywhere: only the sweep can expire it
        assert _wait(
            lambda: ("q.result.tx-idle", b"late") in dead,
            timeout=b_sweep_interval_floor + 2.0,
        )
        assert len(b._pending_q) == 0 and not b._enq_ts
        t.client.close()
    finally:
        b.close()


def test_broker_kv_roundtrip_and_transient():
    from mpcium_tpu.store.broker_kv import BrokerKV

    b = BrokerServer(port=0)
    try:
        t = tcp_transport(b.host, b.port)
        kv = BrokerKV(t.client)
        assert kv.get("mpc_peers/node0") is None
        kv.put("mpc_peers/node0", b"uuid-0")
        kv.put("mpc_peers/node1", b"uuid-1")
        kv.put_transient("ready/node0", b"171000")
        assert kv.get("mpc_peers/node0") == b"uuid-0"
        assert kv.keys("mpc_peers/") == ["mpc_peers/node0", "mpc_peers/node1"]
        assert kv.keys("ready/") == ["ready/node0"]
        kv.delete("mpc_peers/node1")
        assert kv.get("mpc_peers/node1") is None
        assert kv.keys("mpc_peers/") == ["mpc_peers/node0"]
        # binary-safe values
        kv.put("keyinfo/w1", bytes(range(256)))
        assert kv.get("keyinfo/w1") == bytes(range(256))
        t.client.close()
    finally:
        b.close()


def test_broker_kv_journal_durability(tmp_path):
    """Durable keys survive a broker restart via the journal; transient
    (liveness) keys do not."""
    from mpcium_tpu.store.broker_kv import BrokerKV

    journal = str(tmp_path / "q.jsonl")
    b1 = BrokerServer(port=0, journal_path=journal, journal_fsync=False)
    t1 = tcp_transport(b1.host, b1.port)
    kv1 = BrokerKV(t1.client)
    kv1.put("keyinfo/w1", b"meta")
    kv1.put("mpc_peers/node0", b"uuid-0")
    kv1.put_transient("ready/node0", b"hb")
    kv1.delete("mpc_peers/node0")
    t1.client.close()
    b1.close()

    b2 = BrokerServer(port=0, journal_path=journal, journal_fsync=False)
    try:
        t2 = tcp_transport(b2.host, b2.port)
        kv2 = BrokerKV(t2.client)
        assert kv2.get("keyinfo/w1") == b"meta"
        assert kv2.get("mpc_peers/node0") is None  # deleted before restart
        assert kv2.keys("ready/") == []  # transient: not journaled
        t2.client.close()
    finally:
        b2.close()


def test_broker_kv_replicates_to_standby():
    """Durable KV state reaches a hot standby (snapshot + stream) and is
    readable after the client fails over."""
    from mpcium_tpu.store.broker_kv import BrokerKV

    primary = BrokerServer(port=0)
    t = tcp_transport(primary.host, primary.port)
    kv = BrokerKV(t.client)
    kv.put("keyinfo/pre", b"in-snapshot")
    standby = BrokerServer(port=0, follow=(primary.host, primary.port))
    try:
        assert _wait(lambda: standby._rep_synced.is_set())
        assert standby._kv.get("keyinfo/pre") is not None
        kv.put("keyinfo/live", b"streamed")
        kv.put_transient("ready/node0", b"hb")
        assert _wait(lambda: "keyinfo/live" in standby._kv)
        assert "ready/node0" not in standby._kv  # transient: not streamed
        # failover: client configured with both addresses reads from standby
        t2 = tcp_transport(primary.host, primary.port,
                           standbys=[(standby.host, standby.port)])
        kv2 = BrokerKV(t2.client)
        primary.close()
        t.client.close()
        assert _wait(lambda: kv2.get("keyinfo/live") == b"streamed",
                     timeout=15.0)
        assert kv2.get("keyinfo/pre") == b"in-snapshot"
        t2.client.close()
    finally:
        standby.close()
