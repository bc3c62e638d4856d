"""A 2-of-3 deployment with a node out: ``LocalCluster.stop_node`` (a
daemon's SIGTERM sequence, in process), and the served path signing at a
quorum smaller than the committee. Tier-1, CPU, waves of 8 over 16 wallets.

Two references. The signatures: RFC 8032 verification by OpenSSL under
keys OpenSSL made (``benchmark/reference.py``, ``benchmark/wallets.py``: the
program sees only Shamir shares). The quorum's semantics: the plain model
below, which imports nothing of the program.
"""
import itertools
import os
import random
import threading
import time
from collections import defaultdict
from types import SimpleNamespace

import pytest

from benchmark import harness
from mpcium_tpu import wire
from mpcium_tpu.cluster import LocalCluster, load_test_preparams
from mpcium_tpu.consumers.batch_scheduler import BatchSigningScheduler
from mpcium_tpu.node.node import Node, NotEnoughParticipants
from mpcium_tpu.perf import compile_watch
from mpcium_tpu.trace import recorder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCHEME = harness.load_scheme(os.path.join(ROOT, "benchmark"), "ed25519")
WAVE, WALLETS, THRESHOLD = 8, 16, 1
SEED = 3_000_000_038


# -- the plain model ----------------------------------------------------------

class Refused(Exception):
    """Fewer than t + 1 of the wallet's participants are READY."""


def model_quorum(participants, ready, t):
    """Who signs: every READY participant, in order, or nobody."""
    quorum = sorted(set(participants) & set(ready))
    if len(quorum) < t + 1:
        raise Refused(f"{len(quorum)} READY of {sorted(participants)}, "
                      f"need {t + 1}")
    return quorum


def model_leader(participants, ready, t):
    """Whose manifest the batch runs under: the smallest signer."""
    return min(model_quorum(participants, ready, t))


# -- (b) the model against the program, with no cluster ------------------------

class _Registry:
    def __init__(self, ready):
        self._ready = set(ready)

    def ready_peers(self):
        return sorted(self._ready)

    def is_peer_ready(self, peer_id):
        return peer_id in self._ready


def _committees():
    for n, t in itertools.product((3, 5), (1, 2)):
        ids = [f"node{i}" for i in range(n)]
        for size in range(n + 1):
            for ready in itertools.combinations(ids, size):
                yield pytest.param(ids, t, ready,
                                   id=f"{n}nodes-t{t}-{'+'.join(ready)}")


@pytest.mark.parametrize("ids, t, ready", _committees())
def test_the_programs_quorum_and_leader_are_the_models(ids, t, ready):
    registry = _Registry(ready)
    node = SimpleNamespace(registry=registry)
    try:
        want = model_quorum(ids, ready, t)
    except Refused:
        with pytest.raises(NotEnoughParticipants):
            Node._ready_quorum(node, ids, t + 1)
        return
    assert Node._ready_quorum(node, ids, t + 1) == want
    for me in ready:  # each READY node's own view elects the same leader
        scheduler = SimpleNamespace(
            node=SimpleNamespace(node_id=me, registry=registry))
        assert BatchSigningScheduler._acting_leader(
            scheduler, ids) == model_leader(ids, ready, t)


# -- a served cluster at the rehearsal's size ----------------------------------

class _Served:
    """Three nodes, every wallet's share sealed in all three stores, one
    client that signs whole waves and keeps every result event."""

    def __init__(self, root, reply_timeout_s=60.0, batch_window_s=60.0):
        self.cluster = LocalCluster(
            n_nodes=3, threshold=THRESHOLD, root_dir=str(root),
            preparams=load_test_preparams(), batch_signing=True,
            batch_window_s=batch_window_s, reply_timeout_s=reply_timeout_s,
            batch_max_batch=WAVE, loopback_workers=WAVE + 64)
        self.rng = random.Random(SEED)
        self.pubkeys, records = SCHEME.make_wallets(
            WALLETS, self.cluster.node_ids, THRESHOLD, self.rng, {})
        for nid, node in self.cluster.nodes.items():
            for w, record in enumerate(records[nid]):
                node.save_share(record, f"dq-{w}")
        self.waves = 0

    def settle(self, live, timeout_s=10.0):
        """Every live node's registry lists exactly the live nodes."""
        deadline = time.monotonic() + timeout_s
        views = {}
        while time.monotonic() < deadline:
            views = {nid: self.cluster.nodes[nid].registry.ready_peers()
                     for nid in live}
            if all(v == sorted(live) for v in views.values()):
                return
            time.sleep(0.02)
        raise AssertionError(f"registries never settled on {live}: {views}")

    def wave(self, timeout_s=120.0, linger_s=0.0):
        """One wave of WAVE signs for distinct wallets. -> [(wallet,
        digest, [result events of that request])]."""
        wallets = self.rng.sample(range(WALLETS), WAVE)
        digests = [self.rng.randbytes(32) for _ in wallets]
        events = defaultdict(list)
        done = threading.Event()

        def on_result(ev):
            events[ev.tx_id].append(ev)
            if len(events) == WAVE:
                done.set()

        sub = self.cluster.client.on_sign_result(on_result)
        try:
            for i, (w, d) in enumerate(zip(wallets, digests)):
                self.cluster.client.sign_transaction(wire.SignTxMessage(
                    key_type="ed25519", wallet_id=f"dq-{w}",
                    network_internal_code="sol",
                    tx_id=f"dq-{self.waves}-{i}", tx=d,
                    priority=wire.PRIORITY_BULK))
            assert done.wait(timeout_s), (
                f"{len(events)}/{WAVE} requests reached an outcome")
            time.sleep(linger_s)  # a second event would arrive by now
        finally:
            sub.unsubscribe()
        out = [(w, d, events[f"dq-{self.waves}-{i}"])
               for i, (w, d) in enumerate(zip(wallets, digests))]
        self.waves += 1
        return out

    def assert_signed(self, outcomes):
        for w, digest, events in outcomes:
            (ev,) = events  # exactly one terminal outcome
            assert ev.result_type == wire.RESULT_SUCCESS, ev.error_reason
            assert SCHEME.verifies(self.pubkeys[w], digest,
                                   bytes.fromhex(ev.signature))

    def counter_total(self, name):
        return sum(s["counters"].get(name, 0.0)
                   for s in self.cluster.metrics_snapshot().values())


@pytest.fixture()
def served(tmp_path):
    compile_watch.reset()
    s = _Served(tmp_path)
    yield s
    s.cluster.close()
    compile_watch.reset()


def _party_shapes(wait_s=10.0):
    """The batched party's shapes in the compile ledger. The first party
    of a shape writes its entry when IT finishes, which may be after the
    other node's results reached the client: wait for one."""
    deadline = time.monotonic() + wait_s
    while True:
        shapes = sorted({e["shape"] for e in compile_watch.entries()
                         if e["engine"] == SCHEME.ENGINE})
        if shapes or time.monotonic() >= deadline:
            return shapes
        time.sleep(0.02)


def _spans(name):
    return [s for spans, _dropped in recorder.snapshot_all().values()
            for s in spans if s["name"] == name]


def _node_books(snapshot):
    return (snapshot["counters"].get("scheduler.submitted_total", 0.0),
            snapshot["counters"].get("scheduler.batches_fired_total", 0.0),
            snapshot["histograms"].get("store.get_s", {}).get("count", 0))


# -- (a) each node out in turn --------------------------------------------------

@pytest.mark.parametrize("stopped", ["node0", "node1", "node2"])
def test_the_two_nodes_left_sign_and_the_model_names_them(served, stopped):
    cluster = served.cluster
    ids = cluster.node_ids
    live = [nid for nid in ids if nid != stopped]
    cluster.stop_node(stopped)
    served.settle(live)
    books = _node_books(cluster.metrics_snapshot()[stopped])
    recorder.snapshot_all(clear=True)  # the wave's spans alone

    served.assert_signed(served.wave())

    assert _party_shapes() == [f"B{WAVE}|q2"]
    quorum = model_quorum(ids, live, THRESHOLD)
    leader = model_leader(ids, live, THRESHOLD)
    assert quorum == live
    for nid in live:
        assert cluster.nodes[nid]._ready_quorum(ids, THRESHOLD + 1) == quorum
    selected = {s["node"]: s["attrs"] for s in _spans("host:quorum_select")}
    assert set(selected) == set(live)
    for attrs in selected.values():
        assert (attrs["q"], attrs["participants"], attrs["leader"]) == (
            2, 3, leader)
    admitted = {s["node"]: s["attrs"] for s in _spans("host:manifest_admit")}
    assert set(admitted) == set(live)  # one manifest, both took it
    for attrs in admitted.values():
        assert (attrs["outcome"], attrs["leader"]) == ("admitted", leader)
    assert {s["attrs"]["q"] for name in (
        "phase:bsign_nonce_commit", "phase:bsign_aggregate_partial",
        "phase:bsign_combine_verify") for s in _spans(name)} == {2}
    assert served.counter_total("scheduler.deputy_takeover_total") == 0
    assert served.counter_total("scheduler.fallback_total") == 0
    assert served.counter_total("scheduler.batches_fired_total") == 1
    # the absent node took no part, and its sealed share was never read
    snapshot = cluster.metrics_snapshot()
    assert _node_books(snapshot[stopped]) == books
    assert books[2] == 0 and stopped in cluster.nodes
    # what the registries and the cluster wrote down about the departure
    for nid in live:
        own = snapshot[nid]
        assert own["gauges"]["registry.ready_peers"] == 2
        assert own["counters"]["registry.peer_lost_total"] == 1
        assert own["counters"]["registry.peer_joined_total"] == 2
        detect = own["histograms"]["registry.loss_detect_s"]
        # a resignation shows at the next poll, not after the 3 s a
        # crashed peer's heartbeat takes to go stale
        assert detect["count"] == 1 and 0 <= detect["sum"] < 2.0
        sizes = own["histograms"]["scheduler.quorum_size"]
        assert (sizes["count"], sizes["min"], sizes["max"]) == (1, 2, 2)


def test_stop_node_is_the_daemons_shutdown_in_its_order(served):
    cluster = served.cluster
    order = []
    node = cluster.nodes["node1"]
    for label, owner, method in (
            ("signing", cluster.node_signing["node1"], "close"),
            ("consumer", cluster.node_consumers["node1"], "close"),
            ("resign", node.registry, "resign"),
            ("store", node.kvstore, "close")):
        def spy(_real=getattr(owner, method), _label=label):
            order.append(_label)
            return _real()
        setattr(owner, method, spy)
    recorder.snapshot_all(clear=True)
    cluster.stop_node("node1")
    # node/daemon.py run_node: signing.close(), consumer.close(),
    # registry.resign(), then the transport; the store last
    assert order == ["signing", "consumer", "resign", "store"]
    assert cluster.control_kv.get("ready/node1") is None
    (span,) = _spans("cluster:stop_node")
    assert span["node"] == "node1"
    assert set(span["attrs"]) == {"signing_s", "consumer_s", "resign_s",
                                  "transport_s"}
    assert all(v >= 0 for v in span["attrs"].values())
    # its sealed store is still on disk
    assert any((cluster.root / "db" / "node1").iterdir())


# -- (c) below t + 1 ------------------------------------------------------------

def test_below_the_threshold_every_request_is_refused_loudly_once(tmp_path):
    compile_watch.reset()
    s = _Served(tmp_path, reply_timeout_s=0.4, batch_window_s=0.05)
    try:
        s.cluster.stop_node("node0")
        s.cluster.stop_node("node2")
        s.settle(["node1"])
        with pytest.raises(Refused):
            model_quorum(s.cluster.node_ids, ["node1"], THRESHOLD)
        outcomes = s.wave(timeout_s=30.0, linger_s=1.5)
        for _w, _digest, events in outcomes:
            (ev,) = events  # exactly one, and it says no
            assert ev.result_type == wire.RESULT_ERROR
            assert ev.error_reason and not ev.signature
        assert _party_shapes(wait_s=0) == []  # no party was ever built
    finally:
        s.cluster.close()
        compile_watch.reset()


# -- (d) out and back -------------------------------------------------------------

def test_a_stopped_node_comes_back_over_its_store_and_signs(served):
    cluster = served.cluster
    ids = cluster.node_ids
    cluster.stop_node("node0")
    cluster.stop_node("node0")  # nothing left to do
    served.settle(["node1", "node2"])
    served.assert_signed(served.wave())
    assert _party_shapes() == [f"B{WAVE}|q2"]

    compile_watch.reset()
    cluster.respawn_node("node0")
    served.settle(ids)
    recorder.snapshot_all(clear=True)
    served.assert_signed(served.wave())
    assert _party_shapes() == [f"B{WAVE}|q3"]
    assert {s["attrs"]["leader"] for s in _spans("host:manifest_admit")} == {
        model_leader(ids, ids, THRESHOLD)}
    gets = cluster.metrics_snapshot()["node0"]["histograms"]["store.get_s"]
    assert gets["count"] == WAVE  # the shares it sealed before it left

    cluster.stop_node("node0")
    cluster.stop_node("node0")
    cluster.close()  # the fixture closes once more: harmless too
