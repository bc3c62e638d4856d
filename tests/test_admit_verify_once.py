"""Manifest admission verifies an initiator signature only where this
node has not (ISSUE 41): an entry that is byte for byte a request the node
buffered (so verified, at intake) takes that verdict; any other entry is
verified, and a bad one refuses the whole manifest. Unit level: one
follower's scheduler over a loopback fabric, real Ed25519 identities, the
leader's manifests made by hand; ``verify_initiator`` is counted through
a wrapper on the node's identity.
"""
import dataclasses
import threading
import types

import pytest

from mpcium_tpu import trace, wire
from mpcium_tpu.consumers.batch_scheduler import (
    BatchSigningScheduler,
    _manifest_body,
)
from mpcium_tpu.identity.identity import (
    IdentityStore,
    InitiatorKey,
    generate_identity,
)
from mpcium_tpu.trace import recorder
from mpcium_tpu.transport.loopback import LoopbackFabric
from mpcium_tpu.utils import tracing

PEERS = ("n0", "n1", "n2")
N = 4


class _Registry:
    def is_peer_ready(self, p):
        return True

    def ready_count(self):
        return len(PEERS)


@pytest.fixture
def follower(tmp_path, monkeypatch):
    """n1's scheduler (n0 leads by rank, so n1 never fires a manifest of
    its own), the leader's identity, the initiator's key, the list of
    ``verify_initiator`` calls and the list of batches a thread ran."""
    import mpcium_tpu.protocol.ecdsa.batch_signing as ebs

    monkeypatch.setattr(ebs, "quorum_material_digest", lambda share: "dig")
    for nid in PEERS:
        generate_identity(nid, tmp_path)
    initiator = InitiatorKey.generate()
    peers = {nid: nid for nid in PEERS}
    leader, ident = (
        IdentityStore(tmp_path, nid, peers,
                      initiator_pubkey=initiator.public_bytes)
        for nid in ("n0", "n1")
    )
    calls = []
    verify = ident.verify_initiator
    ident.verify_initiator = lambda raw, sig: (
        calls.append(raw), verify(raw, sig))[1]
    info = types.SimpleNamespace(
        participant_peer_ids=PEERS, threshold=1, epoch=0)
    node = types.SimpleNamespace(
        node_id="n1", peer_ids=list(PEERS), registry=_Registry(),
        identity=ident, keyinfo=types.SimpleNamespace(get=lambda kt, w: info),
        load_share=lambda kt, w: types.SimpleNamespace(epoch=0),
    )
    fabric = LoopbackFabric()
    sched = BatchSigningScheduler(
        node, transport=fabric.transport(), window_s=60.0,
        manifest_timeout_s=60.0,
    )
    ran = []
    sched._run_batch = lambda batch_id, reqs, *a: ran.append(batch_id)
    was_armed = trace.armed()
    trace.arm(node_ids=["n1"])
    try:
        yield types.SimpleNamespace(
            sched=sched, leader=leader, initiator=initiator, calls=calls,
            ran=ran)
    finally:
        if not was_armed:
            trace.disarm()
        sched.close()
        fabric.close()


def _signed(initiator, i, key_type="ed25519", **kw):
    msg = wire.SignTxMessage(
        key_type=key_type, wallet_id=f"w{i}", network_internal_code="sol",
        tx_id=f"tx-{i}", tx=bytes([i]) * 32, **kw)
    msg.signature = initiator.sign(msg.raw())
    return msg


def _admit(f, batch_id, msgs):
    """The leader's signed manifest over ``msgs`` through the follower's
    admission; returns the ``host:manifest_admit`` span's attributes once
    the batch thread, if one started, has ended."""
    requests = [{"msg": m.to_json(), "reply": f"reply.{m.tx_id}"}
                for m in msgs]
    man = {"batch_id": batch_id, "leader": "n0", "requests": requests,
           "kind": "sign", "cohorts": 1}
    man["sig"] = f.leader.sign_raw(
        _manifest_body(batch_id, "n0", requests, "sign", 1)).hex()
    t0_ns = tracing.now_ns()
    f.sched._on_manifest_raw(wire.canonical_json(man))
    for t in threading.enumerate():
        if t.name == f"bsign-{batch_id}":
            t.join(10)
    spans, _dropped = recorder.snapshot_all()["n1"]
    (span,) = [s for s in spans if s["name"] == "host:manifest_admit"
               and s["t0_ns"] >= t0_ns and s["attrs"]["batch"] == batch_id]
    return span["attrs"]


def _flip_signature_bit(f, msgs):
    sig = bytearray(msgs[-1].signature)
    sig[7] ^= 0x10
    return msgs[:-1] + [dataclasses.replace(msgs[-1], signature=bytes(sig))]


# case -> (key type, the manifest's entries from the N buffered messages,
# verify_initiator calls, reused, admitted)
CASES = {
    "every_entry_buffered": (
        "ed25519", lambda f, msgs: msgs, 0, N, True),
    "one_request_never_seen": (
        "ed25519", lambda f, msgs: msgs + [_signed(f.initiator, 9)],
        1, N, True),
    "buffered_tx_id_with_other_tx_bytes": (
        "ed25519", lambda f, msgs: msgs[:-1] + [
            dataclasses.replace(msgs[-1], tx=b"\xee" * 32)],
        1, N - 1, False),
    "one_signature_bit_flipped": (
        "ed25519", _flip_signature_bit, 1, N - 1, False),
    "buffered_tx_id_under_another_wallet": (
        "ed25519", lambda f, msgs: msgs[:-1] + [
            dataclasses.replace(msgs[-1], wallet_id="w0")],
        1, N - 1, False),
    "buffered_request_with_another_deadline_and_lane": (
        "ed25519", lambda f, msgs: msgs[:-1] + [
            dataclasses.replace(msgs[-1], deadline_ms=5,
                                priority=wire.PRIORITY_INTERACTIVE)],
        1, N - 1, False),
    "secp256k1_takes_the_same_path": (
        "secp256k1", lambda f, msgs: msgs[:2], 0, 2, True),
}


@pytest.mark.parametrize("case", CASES)
def test_admission_verifies_only_what_the_node_has_not(follower, case):
    f = follower
    key_type, entries, want_calls, want_reused, want_admitted = CASES[case]
    msgs = [_signed(f.initiator, i, key_type) for i in range(N)]
    for m in msgs:
        assert f.sched.submit(m, f"reply.{m.tx_id}")
    manifest = entries(f, msgs)
    reg = f.sched.metrics

    attrs = _admit(f, f"b-{case[:12]}", manifest)

    assert len(f.calls) == want_calls
    assert (attrs["reused"], attrs["verified"]) == (want_reused, want_calls)
    assert attrs["n"] == len(manifest)
    assert reg.counter("batch.admit_verify_reused_total").value == want_reused
    assert reg.counter("batch.admit_verify_checked_total").value == want_calls
    with f.sched._lock:
        left = [e.msg for b in f.sched._buckets.values() for e in b]
    if want_admitted:
        assert attrs["outcome"] == "admitted"
        assert attrs["reused"] + attrs["verified"] == attrs["n"]
        assert f.ran == [f"b-{case[:12]}"]
        covered = {(m.wallet_id, m.tx_id) for m in manifest}
        assert left == [m for m in msgs
                        if (m.wallet_id, m.tx_id) not in covered]
    else:
        # the one entry that is not what this node verified was verified,
        # failed, and took the whole manifest with it: nothing ran, and
        # what the node buffered is where it was
        assert attrs["outcome"] == "bad_initiator_signature"
        assert f.calls == [manifest[-1].raw()]
        assert f.ran == []
        assert left == msgs
