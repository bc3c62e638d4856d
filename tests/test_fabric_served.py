"""The fabric's counters on the served path (ISSUE 26): two waves of 64
signs through a 2-of-3 LocalCluster, every node emitting every result.
The duplicate window must engage on each redundant copy, the waves must
leave no subscription behind, and the client must see each result once,
whichever of a follower's two copies of a request (the ``mpc:sign``
fan-out's, the leader's manifest's) reached it first.

The waves run once (module fixture); every test reads what they left.
"""
import threading
import time
from collections import Counter
from types import SimpleNamespace

import pytest

from mpcium_tpu import wire
from mpcium_tpu.cluster import LocalCluster, load_test_preparams
from mpcium_tpu.engine import eddsa_batch as eb
from mpcium_tpu.trace import recorder
from mpcium_tpu.utils import tracing

N = 64
WAVES = 2


NAMES = {"counters": ("transport.dedup_hits",),
         "gauges": ("transport.dedup_keys", "transport.subscriptions")}


def _fabric_counts(cluster):
    """What each node's registry carries of the fabric's counts."""
    snap = cluster.metrics_snapshot()
    return {nid: {name: s[kind][name] for kind, names in NAMES.items()
                  for name in names if name in s[kind]}
            for nid, s in snap.items()}


def _summed(counts):
    """A snapshot's reading of the one fabric: the sum over its nodes."""
    return {name: sum(c.get(name, 0.0) for c in counts.values())
            for names in NAMES.values() for name in names}


@pytest.fixture(scope="module")
def waves(tmp_path_factory):
    cluster = LocalCluster(
        n_nodes=3, threshold=1,
        root_dir=str(tmp_path_factory.mktemp("fabric-served")),
        preparams=load_test_preparams(),
        batch_signing=True, batch_window_s=120.0, reply_timeout_s=600.0,
        batch_max_batch=N, batch_manifest_timeout_s=600.0,
        loopback_workers=N + 16,
    )
    try:
        t_start_ns = tracing.now_ns()
        ids = cluster.node_ids
        shares = eb.dealer_keygen_batch(N, ids, threshold=1)
        for w in range(N):
            for i, nid in enumerate(ids):
                cluster.nodes[nid].save_share(shares[i][w], f"fs{w}")
        results = Counter()
        failed = []
        done = threading.Event()

        def on_result(ev):
            results[ev.tx_id] += 1
            if ev.result_type != wire.RESULT_SUCCESS:
                failed.append(ev.tx_id)
            if len(results) % N == 0:
                done.set()

        sub = cluster.client.on_sign_result(on_result)
        try:
            before = _fabric_counts(cluster)
            for wave in range(WAVES):
                done.clear()
                for w in range(N):
                    cluster.client.sign_transaction(wire.SignTxMessage(
                        key_type="ed25519", wallet_id=f"fs{w}",
                        network_internal_code="sol",
                        tx_id=f"fs-tx-{wave}-{w}",
                        tx=bytes([wave, w]) * 16, deadline_ms=900_000,
                    ))
                assert done.wait(600), f"{len(results)} results"
            # the client has every result once the first node's copy is
            # through; the other nodes finish their egress, and every
            # session closes, a moment later
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                cluster.fabric.drain(30)
                after = _fabric_counts(cluster)
                if (_summed(after)["transport.dedup_hits"]
                        - _summed(before)["transport.dedup_hits"]
                        >= 2 * WAVES * N
                        and _summed(after)["transport.subscriptions"]
                        <= _summed(before)["transport.subscriptions"]):
                    break
                time.sleep(0.1)
            yield SimpleNamespace(
                ids=ids, results=results, failed=failed,
                before=before, after=after, fabric=cluster.fabric.stats(),
                registries={nid: snap["counters"] for nid, snap
                            in cluster.metrics_snapshot().items()},
                spans={nid: [s for s in spans if s["t0_ns"] >= t_start_ns]
                       for nid, (spans, _d)
                       in recorder.snapshot_all().items()})
        finally:
            sub.unsubscribe()
    finally:
        cluster.close()


def test_each_tx_got_exactly_one_result_event(waves):
    assert not waves.failed
    assert set(waves.results) == {f"fs-tx-{wave}-{w}"
                                  for wave in range(WAVES) for w in range(N)}
    assert set(waves.results.values()) == {1}


def test_every_redundant_result_copy_met_the_window(waves):
    """q nodes each enqueue every result under the tx's key: one passes,
    q - 1 are suppressed, for each request; the client's own enqueues are
    all first sights."""
    quorum = len(waves.ids)
    hits = (_summed(waves.after)["transport.dedup_hits"]
            - _summed(waves.before)["transport.dedup_hits"])
    assert hits == (quorum - 1) * WAVES * N


def test_the_window_holds_one_key_a_request_and_a_result(waves):
    keys = (_summed(waves.after)["transport.dedup_keys"]
            - _summed(waves.before)["transport.dedup_keys"])
    assert keys == 2 * WAVES * N


def test_the_waves_leave_no_subscription_behind(waves):
    """A reply inbox per request and three topics per session node come
    and go; what is subscribed after the waves is what was before."""
    assert (_summed(waves.after)["transport.subscriptions"]
            == _summed(waves.before)["transport.subscriptions"] > 0)


def test_the_one_fabric_is_counted_once_over_the_nodes(waves):
    """The first node's registry carries the fabric's counts and no
    other's does (as with the trace rings no node owns), so the sum over
    a snapshot is the fabric's own reading."""
    first, *others = waves.ids
    assert set(waves.after[first]) == {
        name for names in NAMES.values() for name in names}
    assert all(waves.after[nid] == {} for nid in others)
    own = {**waves.fabric["counters"], **waves.fabric["gauges"]}
    assert _summed(waves.after) == own


def test_a_follower_takes_a_request_from_whichever_copy_comes_first(waves):
    """The leader buffers every request it takes in (``batched``). A
    follower does too, unless the leader's manifest reached it before its
    own ``mpc:sign`` copy: the batch then holds the claim already and the
    late copy reads ``duplicate`` (``_run_batch`` claims for it). Either
    way the request is verified and taken in once on every node, and
    signed once (test_each_tx_got_exactly_one_result_event)."""
    (leader,) = {nid for nid in waves.ids
                 if any(s["name"] == "dispatch" for s in waves.spans[nid])}
    txs = {f"fs-tx-{wave}-{w}" for wave in range(WAVES) for w in range(N)}
    for nid in waves.ids:
        intakes = Counter()
        outcomes = Counter()
        for s in waves.spans[nid]:
            if s["name"] == "intake":
                intakes[s["attrs"]["tx"]] += 1
                outcomes[s["attrs"]["outcome"]] += 1
                assert s["attrs"]["verify_s"] > 0
        assert set(intakes) == txs and set(intakes.values()) == {1}, nid
        allowed = {"batched"} if nid == leader else {"batched", "duplicate"}
        assert set(outcomes) <= allowed, (nid, outcomes)


def test_admission_verifies_only_what_a_node_did_not_take_in(waves):
    """Every manifest entry is one a node verified at intake (``reused``)
    or verifies at admission (``verified``: the manifest beat the node's
    own ``mpc:sign`` copy). The leader fired the manifest from what it had
    buffered, so it verifies nothing twice."""
    (leader,) = {nid for nid in waves.ids
                 if any(s["name"] == "dispatch" for s in waves.spans[nid])}
    for nid in waves.ids:
        admits = [s["attrs"] for s in waves.spans[nid]
                  if s["name"] == "host:manifest_admit"]
        assert len(admits) == WAVES, nid
        for a in admits:
            assert a["outcome"] == "admitted", (nid, a)
            assert a["reused"] + a["verified"] == a["n"] == N, (nid, a)
            if nid == leader:
                assert a["verified"] == 0, a
        reg = waves.registries[nid]
        assert (reg["batch.admit_verify_reused_total"],
                reg["batch.admit_verify_checked_total"]) == (
            sum(a["reused"] for a in admits),
            sum(a["verified"] for a in admits)), nid
