"""Infrastructure layers: wire, identity, loopback transport, stores, registry."""
import threading
import time

import pytest

from mpcium_tpu import wire
from mpcium_tpu.identity.identity import (
    IdentityError,
    IdentityStore,
    InitiatorKey,
    decrypt_private_bytes,
    encrypt_private_bytes,
    generate_identity,
)
from mpcium_tpu.registry.registry import PeerRegistry
from mpcium_tpu.store.keyinfo import KeyInfo, KeyinfoStore
from mpcium_tpu.store.kvstore import EncryptedFileKV, FileKV, MemoryKV
from mpcium_tpu.transport.api import Permanent, TransportError
from mpcium_tpu.transport.loopback import LoopbackFabric, topic_matches
from mpcium_tpu.transport.api import QueueConfig


# -- wire -------------------------------------------------------------------


def test_envelope_roundtrip_and_signing_bytes():
    env = wire.Envelope("w1", "r1", "node0", {"x": "1"}, to="node1", is_broadcast=False)
    rt = wire.Envelope.decode(env.encode())
    assert rt.session_id == "w1" and rt.to == "node1" and rt.payload == {"x": "1"}
    # signature not part of signing bytes
    a = env.marshal_for_signing()
    env.signature = b"\x01" * 64
    assert env.marshal_for_signing() == a


def test_initiator_messages_raw():
    m = wire.SignTxMessage(
        key_type="ed25519", wallet_id="w", network_internal_code="sol",
        tx_id="t1", tx=b"\x01\x02",
    )
    raw1 = m.raw()
    m.signature = b"sig"
    assert m.raw() == raw1  # raw excludes signature
    rt = wire.SignTxMessage.from_json(m.to_json())
    assert rt.tx == b"\x01\x02" and rt.signature == b"sig"


# -- identity ---------------------------------------------------------------


def test_identity_generate_load_sign(tmp_path):
    for n in ("node0", "node1"):
        generate_identity(n, tmp_path)
    store = IdentityStore(tmp_path, "node0", {"node0": "", "node1": ""})
    env = wire.Envelope("w1", "r1", "node0", {"a": "b"})
    store.sign_envelope(env)
    assert store.verify_envelope(env)
    env.payload["a"] = "tampered"
    assert not store.verify_envelope(env)
    # unknown sender
    env2 = wire.Envelope("w1", "r1", "ghost", {})
    env2.signature = b"\x00" * 64
    assert not store.verify_envelope(env2)


def test_identity_encrypted_key(tmp_path):
    with pytest.raises(IdentityError):
        generate_identity("n", tmp_path, passphrase="short")
    generate_identity("node0", tmp_path, passphrase="longpassphrase!x")
    with pytest.raises(IdentityError):
        IdentityStore(tmp_path, "node0", {"node0": ""})  # passphrase missing
    store = IdentityStore(
        tmp_path, "node0", {"node0": ""}, passphrase="longpassphrase!x"
    )
    env = wire.Envelope("s", "r", "node0", {})
    store.sign_envelope(env)
    assert store.verify_envelope(env)


def test_at_rest_encryption_tamper():
    blob = encrypt_private_bytes(b"secret", "pw")
    assert decrypt_private_bytes(blob, "pw") == b"secret"
    with pytest.raises(IdentityError):
        decrypt_private_bytes(blob, "wrong")
    bad = bytearray(blob)
    bad[-1] ^= 1
    with pytest.raises(IdentityError):
        decrypt_private_bytes(bytes(bad), "pw")


def test_initiator_key_roundtrip(tmp_path):
    k = InitiatorKey.generate()
    k.save(tmp_path / "init.key", passphrase="longpassphrase!x")
    k2 = InitiatorKey.load(tmp_path / "init.key", passphrase="longpassphrase!x")
    assert k.public_bytes == k2.public_bytes
    m = wire.GenerateKeyMessage("w1")
    sig = k.sign(m.raw())
    # independent verifier: OpenSSL when available, else the repo's
    # RFC-8032 hostmath implementation (NOT the identity layer under test)
    try:
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PublicKey,
        )

        Ed25519PublicKey.from_public_bytes(k.public_bytes).verify(sig, m.raw())
    except ImportError:
        from mpcium_tpu.core.hostmath import ed25519_verify

        assert ed25519_verify(k.public_bytes, m.raw(), sig)


# -- loopback transport -----------------------------------------------------


def test_topic_matching():
    assert topic_matches("a.b.*", "a.b.c")
    assert topic_matches("x", "x")
    assert not topic_matches("a.b", "a.b.c")


def test_pubsub_fanout():
    f = LoopbackFabric()
    t1, t2 = f.transport(), f.transport()
    got = []
    t1.pubsub.subscribe("topic:x", lambda d: got.append(("t1", d)))
    t2.pubsub.subscribe("topic:x", lambda d: got.append(("t2", d)))
    t1.pubsub.publish("topic:x", b"hello")
    f.drain()
    assert sorted(got) == [("t1", b"hello"), ("t2", b"hello")]
    f.close()


def test_direct_ack_and_failure():
    f = LoopbackFabric()
    t = f.transport()
    got = []
    t.direct.listen("direct:n1", lambda d: got.append(d))
    t.direct.send("direct:n1", b"ping")  # blocks until handled
    assert got == [b"ping"]
    with pytest.raises(TransportError):
        f.direct_send("direct:nobody", b"x", timeout_s=0.05, attempts=2,
                      retry_delay_s=0.01)
    f.close()


def test_queue_redelivery_and_dead_letter():
    f = LoopbackFabric(QueueConfig(max_deliver=3))
    t = f.transport()
    dead = []
    t.set_dead_letter_handler(lambda topic, data, n: dead.append((topic, data, n)))
    attempts = []

    def failing(data):
        attempts.append(data)
        raise RuntimeError("boom")

    t.queues.dequeue("q.fail.*", failing)
    t.queues.enqueue("q.fail.1", b"m")
    f.drain()
    assert len(attempts) == 3  # max_deliver
    assert dead == [("q.fail.1", b"m", 3)]

    # Permanent terminates without dead-letter
    perm = []

    def perm_handler(data):
        perm.append(data)
        raise Permanent()

    t.queues.dequeue("q.perm.*", perm_handler)
    t.queues.enqueue("q.perm.1", b"p")
    f.drain()
    assert len(perm) == 1 and len(dead) == 1
    f.close()


def test_queue_idempotency_and_pending():
    f = LoopbackFabric()
    t = f.transport()
    got = []
    # enqueue BEFORE any consumer exists — must be buffered (durable)
    t.queues.enqueue("q.r.1", b"early", idempotency_key="k1")
    t.queues.enqueue("q.r.1", b"early-dup", idempotency_key="k1")  # deduped
    t.queues.dequeue("q.r.*", lambda d: got.append(d))
    f.drain()
    assert got == [b"early"]
    t.queues.enqueue("q.r.2", b"late", idempotency_key="k2")
    f.drain()
    assert got == [b"early", b"late"]
    f.close()


def test_handler_can_send_direct_without_deadlock():
    f = LoopbackFabric()
    t = f.transport()
    got = []
    t.direct.listen("direct:b", lambda d: got.append(d))
    # a pubsub handler that performs a blocking acked unicast
    t.pubsub.subscribe("go", lambda d: t.direct.send("direct:b", d + b"!"))
    t.pubsub.publish("go", b"chain")
    f.drain()
    assert got == [b"chain!"]
    f.close()


# -- stores -----------------------------------------------------------------


def test_encrypted_kv(tmp_path):
    with pytest.raises(ValueError):
        EncryptedFileKV(tmp_path / "db", "")  # password mandatory
    kv = EncryptedFileKV(tmp_path / "db", "pw123")
    kv.put("ecdsa:w1", b"share-data")
    kv.put("eddsa:w1", b"other")
    assert kv.get("ecdsa:w1") == b"share-data"
    assert kv.keys("ecdsa:") == ["ecdsa:w1"]
    # on-disk bytes are ciphertext
    blobs = [
        p.read_bytes()
        for p in (tmp_path / "db").iterdir()
        if p.name != ".salt"
    ]
    assert blobs and all(b"share-data" not in b for b in blobs)
    # reopen with right/wrong password
    kv2 = EncryptedFileKV(tmp_path / "db", "pw123")
    assert kv2.get("ecdsa:w1") == b"share-data"
    with pytest.raises(ValueError, match="wrong encryption password"):
        EncryptedFileKV(tmp_path / "db", "wrong")
    kv.delete("ecdsa:w1")
    assert kv.get("ecdsa:w1") is None and kv.keys("ecdsa:") == []


def test_keyinfo_store():
    ks = KeyinfoStore(MemoryKV())
    info = KeyInfo(["a", "b", "c"], threshold=1, public_key="aa", vss_commitments=["bb"])
    ks.save("secp256k1", "w1", info)
    got = ks.get("secp256k1", "w1")
    assert got == info
    assert ks.get("ed25519", "w1") is None
    # key prefix matches reference scheme
    assert ks.kv.keys() == ["threshold_keyinfo/ecdsa:w1"]


def test_file_kv(tmp_path):
    kv = FileKV(tmp_path / "kv")
    kv.put("mpc_peers/node0", b"id0")
    kv.put("ready/node0", b"true")
    assert kv.keys("ready/") == ["ready/node0"]
    assert kv.get("mpc_peers/node0") == b"id0"
    kv.delete("ready/node0")
    assert kv.keys("ready/") == []


# -- registry ---------------------------------------------------------------


def test_registry_ready_flow():
    kv = MemoryKV()
    ids = ["n0", "n1", "n2"]
    regs = {n: PeerRegistry(n, ids, kv, poll_interval_s=0.02) for n in ids}
    regs["n0"].ready()
    assert regs["n0"].ready_count() == 1
    assert not regs["n0"].all_ready()
    for n in ("n1", "n2"):
        regs[n].ready()
    assert regs["n0"].wait_all_ready(timeout_s=2)
    assert regs["n0"].ready_peers() == ids
    # resign → peers notice
    regs["n2"].resign()
    regs["n0"]._poll_once()
    assert not regs["n0"].all_ready()
    assert regs["n0"].ready_peers() == ["n0", "n1"]


@pytest.mark.parametrize("make_kv", [MemoryKV, FileKV], ids=["memory", "file"])
def test_registry_poll_asks_for_its_peers_keys_and_lists_nothing(
        tmp_path, make_kv):
    """A poll costs a get a peer whatever else the control plane holds (a
    keyinfo entry a wallet: listing them all, 60 times a second over three
    nodes, kept the interpreter busy at 131,072 wallets; PERF.md, PR 35).
    A stranger's ``ready/`` key is no peer."""
    kv = make_kv(tmp_path) if make_kv is FileKV else make_kv()
    ids = ["n0", "n1", "n2"]
    regs = {n: PeerRegistry(n, ids, kv, poll_interval_s=30.0) for n in ids}
    for n in ids:
        regs[n].ready()
    kv.put("ready/stranger", kv.get("ready/n1"))
    for w in range(50):
        kv.put(f"threshold_keyinfo/eddsa:w{w}", b"{}")
    asked = []
    kv.keys = lambda prefix="": asked.append(("keys", prefix)) or []
    real_get = kv.get
    kv.get = lambda key: asked.append(("get", key)) or real_get(key)
    regs["n0"]._poll_once()
    assert sorted(asked) == [("get", f"ready/{n}") for n in ids]
    assert regs["n0"].all_ready() and regs["n0"].ready_peers() == ids
    regs["n2"].resign()
    regs["n0"]._poll_once()
    assert regs["n0"].ready_peers() == ["n0", "n1"]


def test_remote_cluster_loads_key_before_connecting(tmp_path):
    """A missing initiator key must fail BEFORE any broker connection is
    attempted (no leaked authenticated connection + reader thread): with
    no broker listening, connecting first would surface a connection
    error instead of the key error."""
    import pytest

    from mpcium_tpu.cluster import RemoteCluster

    cfg = tmp_path / "config.yaml"
    cfg.write_text("broker_host: 127.0.0.1\nbroker_port: 1\n")
    with pytest.raises(FileNotFoundError):
        RemoteCluster(str(cfg))
