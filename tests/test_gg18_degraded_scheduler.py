"""A 2-of-3 secp256k1 deployment with node0 out, on the served path down to
the party, in tier-1: client -> fabric -> event consumer -> batch scheduler
(``_run_batch``: the shared quorum selection, the deputy's manifest) ->
``Session`` -> the batched ECDSA party, which a recording fake REPLACES,
because XLA:CPU cannot compile the real party's MtA programs inside
tier-1 (its curve side at q = 2 is ``tests/test_gg18_subset_quorum.py``;
the whole party is the slow tier's, through the degraded cell's own
rehearsal). The fake signs by the plain reference beside this file
(``gg18_subset_reference.py``: the signers tell each other their nonces
and additive shares, which no real party does), so every request still
comes back with an ``r ‖ s`` that OpenSSL accepts under the wallet's key.

Held here: the secp256k1 bucket fires under node1's manifest, the quorum
is ``[node1, node2]`` at both live nodes, ``host:quorum_select`` says
``q=2``, each live node's party is handed ITS scheduler's context cache
and asks it for the two live parties' contexts only, the stopped node's
books stand still, and a stopped node's context cache is empty (SECURITY.md,
"Key material in memory": a stopped daemon holds no key material in device
memory)."""
import os
import random
import threading
import time

import numpy as np
import pytest

import gg18_subset_reference as ref
from benchmark import harness
from mpcium_tpu import wire
from mpcium_tpu.cluster import LocalCluster, load_test_preparams
from mpcium_tpu.perf import compile_watch
from mpcium_tpu.protocol.base import PartyBase, party_xs
from mpcium_tpu.protocol.ecdsa import batch_signing
from mpcium_tpu.trace import recorder
from test_degraded_quorum import (_node_books, _spans, model_leader,
                                  model_quorum)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCHEME = harness.load_scheme(os.path.join(ROOT, "benchmark"), "secp256k1")
WAVE, WALLETS, THRESHOLD = 4, 8, 1
SEED = 3_000_000_045
N = ref.N


class _PlainParty(PartyBase):
    """Stands where ``BatchedECDSASigningParty`` would, with its
    constructor's arguments: asks the context cache it is handed for what
    the real party asks (its own private context, a public one a peer),
    with a build that touches no device, and signs in one broadcast round
    by the plain reference. ``built`` keeps what each was constructed
    with."""

    built = []

    def __init__(self, session_id, self_id, party_ids, shares, digests,
                 dom=None, rng=None, cohorts=None, metrics=None,
                 contexts=None):
        super().__init__(session_id, self_id, party_ids)
        first = shares[0]
        material = batch_signing.quorum_material_digest(first)
        asked = [("private", self_id, material, first.epoch)] + [
            ("public", p, material, first.epoch) for p in self.others()]
        for key in asked:
            contexts.get(key, object)  # a context that is no device array
        xs = party_xs(first.participants)
        at = [xs[p] for p in self.party_ids]
        lam = ref.lagrange_at_zero(at, xs[self_id])
        draw = random.Random(f"{session_id}:{self_id}")
        self._m = [int.from_bytes(d, "big") % N for d in digests]
        self._own = {"k": [draw.randrange(1, N) for _ in shares],
                     "g": [draw.randrange(1, N) for _ in shares],
                     "w": [lam * s.share % N for s in shares]}
        _PlainParty.built.append({
            "self_id": self_id, "party_ids": list(self.party_ids),
            "contexts": contexts, "asked": asked, "lanes": len(shares)})

    def start(self):
        return [self.broadcast("plain/1", {
            f: [str(v) for v in vals] for f, vals in self._own.items()})]

    def receive(self, msg):
        if self.done:
            return []
        self._store(msg)
        if not self._round_full("plain/1", self.others()):
            return []
        told = {self.self_id: self._own}
        for p, payload in self._round_payloads("plain/1").items():
            told[p] = {f: [int(v) for v in vals]
                       for f, vals in payload.items()}
        signed = [ref.sign(m, *({p: told[p][f][i] for p in self.party_ids}
                                for f in ("k", "g", "w")))
                  for i, m in enumerate(self._m)]

        def block(name):
            return np.stack([np.frombuffer(
                s[name].to_bytes(32, "big"), np.uint8) for s in signed])

        self.result = {
            "r": block("r"), "s": block("s_low"),
            "recovery": np.array([s["recovery"] for s in signed]),
            "ok": np.ones((len(signed),), bool)}
        self.done = True
        return []


@pytest.fixture()
def served(tmp_path, monkeypatch):
    compile_watch.reset()
    monkeypatch.setattr(batch_signing, "BatchedECDSASigningParty",
                        _PlainParty)
    _PlainParty.built = []
    fixtures = load_test_preparams(bits=1024)
    have = [fixtures[k] for k in sorted(fixtures)]
    preparams = {f"node{i}": have[i] for i in range(3)}
    cluster = LocalCluster(
        n_nodes=3, threshold=THRESHOLD, root_dir=str(tmp_path),
        preparams=preparams, batch_signing=True, batch_window_s=60.0,
        reply_timeout_s=60.0, batch_max_batch=WAVE,
        loopback_workers=WAVE + 64)
    rng = random.Random(SEED)
    pubkeys, records = SCHEME.make_wallets(
        WALLETS, cluster.node_ids, THRESHOLD, rng, preparams)
    for nid, node in cluster.nodes.items():
        for w, record in enumerate(records[nid]):
            node.save_share(record, f"gq-{w}")
    yield cluster, pubkeys, rng
    cluster.close()
    compile_watch.reset()


def _settle(cluster, live):
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        if all(cluster.nodes[nid].registry.ready_peers() == sorted(live)
               for nid in live):
            return
        time.sleep(0.02)
    raise AssertionError(f"registries never settled on {live}")


def _wave(cluster, rng, tag, timeout_s=60.0):
    """One wave of WAVE ECDSA signs for distinct wallets ->
    [(wallet, digest, result event)]."""
    wallets = rng.sample(range(WALLETS), WAVE)
    digests = [rng.randbytes(32) for _ in wallets]
    events, done = {}, threading.Event()

    def on_result(ev):
        events[ev.tx_id] = ev
        if len(events) == WAVE:
            done.set()

    sub = cluster.client.on_sign_result(on_result)
    try:
        for i, (w, d) in enumerate(zip(wallets, digests)):
            cluster.client.sign_transaction(wire.SignTxMessage(
                key_type="secp256k1", wallet_id=f"gq-{w}",
                network_internal_code="eth", tx_id=f"{tag}-{i}", tx=d,
                priority=wire.PRIORITY_BULK))
        assert done.wait(timeout_s), f"{len(events)}/{WAVE} outcomes"
    finally:
        sub.unsubscribe()
    return [(w, d, events[f"{tag}-{i}"])
            for i, (w, d) in enumerate(zip(wallets, digests))]


def _counter_total(cluster, name):
    return sum(s["counters"].get(name, 0.0)
               for s in cluster.metrics_snapshot().values())


def test_a_secp256k1_bucket_signs_at_q2_under_the_deputys_manifest(served):
    cluster, pubkeys, rng = served
    ids = cluster.node_ids
    live = ["node1", "node2"]
    caches = {nid: cluster.node_consumers[nid].scheduler.gg18_contexts()
              for nid in ids}
    # what a warm batch (the benchmark's runs node0 and node1) leaves in
    # the node that is about to go
    caches["node0"].get(("private", "node0", "warm", 0), object)
    caches["node0"].get(("public", "node1", "warm", 0), object)
    assert len(caches["node0"]) == 2

    cluster.stop_node("node0")
    _settle(cluster, live)
    assert len(caches["node0"]) == 0  # a stopped node keeps no context
    books = _node_books(cluster.metrics_snapshot()["node0"])
    recorder.snapshot_all(clear=True)

    outcomes = _wave(cluster, rng, "gq-a")

    for w, digest, ev in outcomes:
        assert ev.result_type == wire.RESULT_SUCCESS, ev.error_reason
        signature = bytes.fromhex(SCHEME.result_signature(ev))
        assert SCHEME.verifies(pubkeys[w], digest, signature)
        assert not SCHEME.high_s(signature)
        assert int(ev.signature_recovery, 16) in (0, 1, 2, 3)
    # who signed, and under whose manifest: the plain model's answer
    quorum = model_quorum(ids, live, THRESHOLD)
    leader = model_leader(ids, live, THRESHOLD)
    assert (quorum, leader) == (live, "node1")
    built = {b["self_id"]: b for b in _PlainParty.built}
    assert set(built) == set(live)
    for nid, b in built.items():
        assert b["party_ids"] == quorum and b["lanes"] == WAVE
        # its own scheduler's cache, asked for the live parties' only
        assert b["contexts"] is caches[nid]
        assert [(kind, pid) for kind, pid, _m, _e in b["asked"]] == [
            ("private", nid)] + [("public", p) for p in live if p != nid]
        assert len(caches[nid]) == 2
    assert len({b["asked"][0][2] for b in built.values()}) == 1  # one bucket
    selected = {s["node"]: s["attrs"] for s in _spans("host:quorum_select")}
    assert set(selected) == set(live)
    for attrs in selected.values():
        assert (attrs["q"], attrs["participants"], attrs["leader"]) == (
            2, 3, leader)
    admitted = {s["node"]: s["attrs"] for s in _spans("host:manifest_admit")}
    assert set(admitted) == set(live)
    for attrs in admitted.values():
        assert (attrs["outcome"], attrs["leader"]) == ("admitted", leader)
    assert _counter_total(cluster, "scheduler.batches_fired_total") == 1
    assert _counter_total(cluster, "scheduler.deputy_takeover_total") == 0
    assert _counter_total(cluster, "scheduler.fallback_total") == 0
    # the absent node took no part, its sealed shares unread, its cache
    # still empty
    assert _node_books(cluster.metrics_snapshot()["node0"]) == books
    assert books[2] == 0 and len(caches["node0"]) == 0
    # a second wave finds the contexts where the first left them
    hits = _counter_total(cluster, "party.ecdsa.context_hits_total")
    misses = _counter_total(cluster, "party.ecdsa.context_misses_total")
    _wave(cluster, rng, "gq-b")
    assert _counter_total(
        cluster, "party.ecdsa.context_misses_total") == misses
    assert _counter_total(
        cluster, "party.ecdsa.context_hits_total") == hits + 4


def test_with_every_node_ready_the_same_bucket_signs_at_q3(served):
    """The control: nobody stopped, the party is built over the whole
    committee under node0's manifest, and each node asks for three
    contexts."""
    cluster, pubkeys, rng = served
    ids = cluster.node_ids
    recorder.snapshot_all(clear=True)
    for w, digest, ev in _wave(cluster, rng, "gq-c"):
        assert ev.result_type == wire.RESULT_SUCCESS, ev.error_reason
        assert SCHEME.verifies(pubkeys[w], digest,
                               bytes.fromhex(SCHEME.result_signature(ev)))
    built = {b["self_id"]: b for b in _PlainParty.built}
    assert set(built) == set(ids)
    assert all(b["party_ids"] == ids and len(b["asked"]) == 3
               for b in built.values())
    assert {s["attrs"]["leader"] for s in _spans("host:quorum_select")} == {
        "node0"}
