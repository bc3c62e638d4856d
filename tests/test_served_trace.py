"""The served host path's stage spans and counters (ISSUE 25): one 2-of-3
batch of 64 signs through LocalCluster, client SDK to result event, read
back from the flight recorders and the nodes' registries.

The batch runs once (module fixture); every test reads what it left.
"""
import threading
import time
from collections import Counter
from types import SimpleNamespace

import pytest

from mpcium_tpu import trace, wire
from mpcium_tpu.cluster import LocalCluster, load_test_preparams
from mpcium_tpu.engine import eddsa_batch as eb
from mpcium_tpu.trace import recorder
from mpcium_tpu.utils import tracing

N = 64
BATCH_SPANS = ("host:manifest_admit", "host:batch_prepare", "wait:hello",
               "host:result_egress", "session")
LAST_TX = f"st-tx-{N - 1}"
CLOCK_STEP_S = 0.010  # the coarsest thread CPU clock seen (a 10 ms tick)


def _intakes(nid):
    spans, _dropped = recorder.snapshot_all().get(nid, ([], 0))
    return [s for s in spans if s["name"] == "intake"]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    cluster = LocalCluster(
        n_nodes=3, threshold=1,
        root_dir=str(tmp_path_factory.mktemp("served-trace")),
        preparams=load_test_preparams(),
        batch_signing=True, batch_window_s=120.0, reply_timeout_s=600.0,
        batch_max_batch=N, batch_manifest_timeout_s=600.0,
        loopback_workers=N + 16,
    )
    try:
        ids = cluster.node_ids
        # arming resets the nodes' rings only: the shared `client` ring
        # still holds what an earlier module of this worker submitted
        # (under `--dist loadfile` that was now and then another cluster's
        # `client:submit` spans, and the tx sets below did not match)
        recorder.recorder_for("client").snapshot(clear=True)
        shares = eb.dealer_keygen_batch(N, ids, threshold=1)
        for w in range(N):
            for i, nid in enumerate(ids):
                cluster.nodes[nid].save_share(shares[i][w], f"st{w}")
        anchors = []
        tracing.set_clock_anchor_hook(anchors.append)
        results = {}
        done = threading.Event()

        def on_result(ev):
            results[ev.tx_id] = ev
            if len(results) == N:
                done.set()

        def submit(w):
            cluster.client.sign_transaction(wire.SignTxMessage(
                key_type="ed25519", wallet_id=f"st{w}",
                network_internal_code="sol", tx_id=f"st-tx-{w}",
                tx=bytes([w]) * 32, deadline_ms=900_000,
            ))

        sub = cluster.client.on_sign_result(on_result)
        try:
            for w in range(N - 1):
                submit(w)
            # the leader fires its manifest at its Nth request, and a
            # follower whose own copy of a request comes after that
            # manifest's batch has claimed it takes it in as `duplicate`,
            # by design. Hold the last request back until every node has
            # taken in the others, so that none of THEIR copies races the
            # manifest (under load one did, now and then)
            deadline = time.monotonic() + 120
            while (min(len(_intakes(nid)) for nid in ids) < N - 1
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            assert min(len(_intakes(nid)) for nid in ids) == N - 1
            submit(N - 1)
            assert done.wait(600), f"{len(results)}/{N} results"
        finally:
            sub.unsubscribe()
        # the first node's result event reaches the client; the other
        # nodes finish their own egress a moment later
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            snap = recorder.snapshot_all()
            if all(any(s["name"] == "host:result_egress"
                       for s in snap.get(nid, ([], 0))[0]) for nid in ids):
                break
            time.sleep(0.1)
        cluster.fabric.drain(30)
        yield SimpleNamespace(
            cluster=cluster, ids=ids, results=results, anchors=anchors,
            spans={nid: spans for nid, (spans, _d)
                   in recorder.snapshot_all().items()},
            metrics=cluster.metrics_snapshot(),
        )
    finally:
        cluster.close()


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def test_every_signature_came_back(served):
    assert len(served.results) == N
    assert all(ev.result_type == wire.RESULT_SUCCESS
               for ev in served.results.values())


def test_every_stage_span_on_every_node_with_the_batch_and_trace_id(served):
    (dispatch,) = [s for nid in served.ids
                   for s in _named(served.spans[nid], "dispatch")]
    batch = dispatch["attrs"]["batch"]
    sid = f"bsign:{batch}"
    for nid in served.ids:
        spans = served.spans[nid]
        for name in BATCH_SPANS:
            (s,) = _named(spans, name)
            assert s["tid"] == sid, (nid, name)
            assert s["trace_id"] == tracing.trace_id_for(sid), (nid, name)
            assert s["t1_ns"] >= s["t0_ns"]
        for name in ("host:manifest_admit", "host:batch_prepare",
                     "host:result_egress"):
            (s,) = _named(spans, name)
            assert s["attrs"]["batch"] == batch and s["attrs"]["n"] == N
        (admit,) = _named(spans, "host:manifest_admit")
        assert admit["attrs"]["outcome"] == "admitted"
        assert admit["attrs"]["verify_s"] > 0 and admit["attrs"]["parse_s"] > 0
        (prep,) = _named(spans, "host:batch_prepare")
        assert prep["attrs"]["load_s"] > 0 and prep["attrs"]["party_s"] > 0
        (egress,) = _named(spans, "host:result_egress")
        assert egress["attrs"]["enqueue_s"] > 0
        # admit -> prepare -> hello wait -> rounds -> session end -> egress
        (hello,) = _named(spans, "wait:hello")
        (session,) = _named(spans, "session")
        assert admit["t0_ns"] <= prep["t0_ns"] <= hello["t0_ns"]
        assert hello["t1_ns"] <= session["t1_ns"] <= egress["t0_ns"]
        inbound = _named(spans, "host:envelope_in")
        assert {s["attrs"]["sender"] for s in inbound} == (
            set(served.ids) - {nid})
        assert all(s["tid"] == sid and s["attrs"]["round"] for s in inbound)


def test_an_inbound_envelope_is_the_parent_of_the_round_it_causes(served):
    for nid in served.ids:
        spans = served.spans[nid]
        rounds = {s["parent_id"]: s for s in spans
                  if s["name"].startswith("round:eddsa")}
        delivered = [s for s in _named(spans, "host:envelope_in")
                     if not s["attrs"]["buffered"]]
        assert delivered
        for env in delivered:
            caused = rounds[env["span_id"]]
            assert caused["name"] == "round:" + env["attrs"]["round"]
            assert env["t1_ns"] <= caused["t0_ns"]  # no time counted twice


def test_tx_joins_client_submit_intake_and_queue(served):
    txs = {f"st-tx-{w}" for w in range(N)}
    client = served.spans["client"]
    submits = {s["attrs"]["tx"]: s for s in _named(client, "client:submit")}
    assert set(submits) == txs
    assert all(s["tid"] == "sign" and 0 < s["attrs"]["sign_s"]
               <= (s["t1_ns"] - s["t0_ns"]) / 1e9 for s in submits.values())
    results = Counter(s["attrs"]["tx"] for s in _named(client, "client:result"))
    assert set(results) == txs
    (leader,) = {s["node"] for nid in served.ids
                 for s in _named(served.spans[nid], "dispatch")}
    for nid in served.ids:
        intakes = {s["attrs"]["tx"]: s
                   for s in _named(served.spans[nid], "intake")}
        assert set(intakes) == txs
        for tx, s in intakes.items():
            assert s["kind"] == "X" and s["tid"] == "lane:bulk"
            assert s["attrs"]["req_kind"] == "sign"
            assert s["attrs"]["deadline_ms"] == 900_000
            # every node had taken in all but the last request before the
            # leader could fire; a follower's copy of the last one may
            # come after the manifest's batch claimed it (`duplicate`)
            late = tx == LAST_TX and nid != leader
            assert s["attrs"]["outcome"] in (
                ("batched", "duplicate") if late else ("batched",)), (nid, tx)
            assert s["attrs"]["verify_s"] > 0
            assert s["t0_ns"] >= submits[tx]["t0_ns"]
    queued = [s for nid in served.ids
              for s in _named(served.spans[nid], "queue")
              if s["attrs"]["outcome"] == "dispatched"]
    assert {s["attrs"]["tx"] for s in queued} == txs
    assert len({s["attrs"]["batch"] for s in queued}) == 1


@pytest.mark.parametrize("name,per_node", [
    ("intake.handle_s", N),
    ("intake.verify_initiator_s", N),
    ("batch.share_load_s", N),
    ("batch.manifest_admit_s", 1),
    ("batch.prepare_s", 1),
    ("egress.result_s", 1),
])
def test_a_stage_histogram_counts_requests_or_batches_per_node(
        served, name, per_node):
    for nid in served.ids:
        h = served.metrics[nid]["histograms"][name]
        assert h["count"] == per_node, (nid, name)
        assert h["sum"] > 0


@pytest.mark.parametrize("name,per_node", [
    ("host:manifest_admit", 1),
    ("host:batch_prepare", 1),
    ("host:result_egress", 1),
    ("phase:bsign_nonce_commit", 1),
    ("phase:bsign_aggregate_partial", 1),
    ("phase:bsign_combine_verify", 1),
])
def test_a_batch_level_span_carries_its_threads_cpu_seconds_on_every_node(
        served, name, per_node):
    """``cpu_s``: what the span's own thread ran between its two ends, so
    never more than the span lasted (give or take one step of the clock)."""
    for nid in served.ids:
        spans = _named(served.spans[nid], name)
        assert len(spans) == per_node, (nid, name)
        for s in spans:
            wall_s = (s["t1_ns"] - s["t0_ns"]) / 1e9
            assert 0 <= s["attrs"]["cpu_s"] <= wall_s + CLOCK_STEP_S, (nid, s)


def test_client_submit_carries_cpu_seconds_and_no_other_request_span_does(
        served):
    submits = _named(served.spans["client"], "client:submit")
    assert len(submits) == N
    for s in submits:
        wall_s = (s["t1_ns"] - s["t0_ns"]) / 1e9
        assert 0 <= s["attrs"]["cpu_s"] <= wall_s + CLOCK_STEP_S
    # the SDK ran: the window's sum is no clock artefact
    assert sum(s["attrs"]["cpu_s"] for s in submits) > 0
    for ring in served.spans.values():
        for s in ring:
            if s["name"] in ("client:result", "intake", "queue", "session",
                             "host:envelope_in", "host:quorum_select",
                             "wait:hello", "dispatch") or s["name"].startswith(
                                 "round:"):
                assert "cpu_s" not in s["attrs"], s["name"]


def test_the_interpreter_account_stands_in_the_first_nodes_snapshot_only(
        served):
    first, others = served.ids[0], served.ids[1:]
    own = served.metrics[first]
    roles = {k[len("interp.cpu_s."):] for k in own["gauges"]
             if k.startswith("interp.cpu_s.")}
    # the caller, the fabric's two pools, a batch thread and a sender a node
    assert {"main", "loopback", "loopback-q", "bsign", "send"} <= roles
    assert own["gauges"]["interp.cpu_s.bsign"] > 0
    assert own["gauges"]["interp.cpu_s.loopback"] > 0
    assert own["gauges"]["interp.threads.loopback-q"] >= 1
    assert own["histograms"]["interp.handover_lag_s"]["count"] >= 10
    assert own["counters"]["log.lines_total"] >= N  # "signing requested"
    assert own["counters"]["log.emit_s_total"] > 0
    for nid in others:
        for kind in ("gauges", "counters", "histograms"):
            assert not [k for k in served.metrics[nid][kind]
                        if k.startswith(("interp.", "log."))], (nid, kind)


@pytest.mark.parametrize("name", ["transport.queue_wait_s",
                                  "bridge.reply_wait_s"])
def test_the_bridges_of_all_nodes_share_the_requests(served, name):
    """The durable queue balances the requests over the nodes' bridges:
    each request waited for one queue worker and one reply."""
    counts = [served.metrics[nid]["histograms"][name]["count"]
              for nid in served.ids]
    assert sum(counts) == N and min(counts) >= 1


def test_pubsub_waits_cover_sign_copies_manifest_and_rounds(served):
    for nid in served.ids:
        h = served.metrics[nid]["histograms"]["transport.pubsub_wait_s"]
        # N mpc:sign copies, the manifest, and the session's envelopes
        assert h["count"] >= N + 1 + 6, nid
        assert h["min"] >= 0


def test_bridge_inflight_returns_to_zero_and_peaked(served):
    peaks = []
    for nid in served.ids:
        g = served.metrics[nid]["gauges"]
        assert g["bridge.inflight"] == 0
        peaks.append(g["bridge.inflight_peak"])
    assert min(peaks) >= 1 and sum(peaks) <= N


def test_a_batch_leaves_tens_of_batch_spans_on_a_ring(served):
    for nid in served.ids:
        names = Counter(s["name"] for s in served.spans[nid])
        assert sum(names.values()) <= 2 * N + 40, (nid, names)
        per_request = names["intake"] + names["queue"]
        assert sum(names.values()) - per_request <= 40, (nid, names)


def test_the_leader_anchors_the_clock_once_a_dispatch(served):
    (dispatch,) = [s for nid in served.ids
                   for s in _named(served.spans[nid], "dispatch")]
    (anchor,) = served.anchors
    assert dispatch["t0_ns"] <= anchor <= dispatch["t1_ns"]


def test_dropped_spans_reach_the_snapshot_without_health(served):
    """Every ring once, the shared tracks included; monotone; no call to
    health() needed."""
    cluster = served.cluster
    before = sum(s["gauges"]["trace.dropped_spans"]
                 for s in cluster.metrics_snapshot().values())
    ring = recorder.recorder_for("engine")
    held, _ = ring.snapshot()
    for i in range(ring.capacity - len(held) + 3):
        ring.record({"name": f"filler{i}", "node": "engine"})
    own = recorder.recorder_for(served.ids[1])
    held, _ = own.snapshot()
    for i in range(own.capacity - len(held) + 2):
        own.record({"name": f"filler{i}", "node": served.ids[1]})
    recorder.snapshot_all(clear=True)
    snap = cluster.metrics_snapshot()
    assert sum(s["gauges"]["trace.dropped_spans"]
               for s in snap.values()) == before + 5
    assert snap[served.ids[1]]["gauges"]["trace.dropped_spans"] == 2


def test_dropped_total_survives_clear_and_counts_a_forced_wrap():
    rec = recorder.FlightRecorder("n0", capacity=4)
    for i in range(10):
        rec.record({"name": f"s{i}"})
    assert rec.dropped_total == 6
    assert rec.snapshot(clear=True)[1] == 6
    assert rec.snapshot() == ([], 0) and rec.dropped_total == 6
    for i in range(5):
        rec.record({"name": f"t{i}"})
    assert rec.dropped_total == 7 and rec.snapshot()[1] == 1


def test_clock_anchor_without_a_profiler_and_the_exports_base():
    trace.arm(node_ids=["anchor-node"])
    try:
        tracing.clock_anchor()  # no capture runs: nothing to write, no error
        tracing.emit("queue", 5_000, 9_000, node="anchor-node")
        tracing.emit("dispatch", 7_000, 9_500, node="anchor-node")
        doc = trace.snapshot_chrome(node_ids=["anchor-node"])
    finally:
        trace.disarm()
    assert doc["otherData"]["monotonic_base_ns"] == 5_000
    by_name = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert by_name["dispatch"]["ts"] == pytest.approx(2.0)
    tracing.clock_anchor()  # disarmed: the hook is gone with the sink


def test_traced_and_untraced_transcripts_stay_identical(monkeypatch):
    """scripts/trace_check.py's check, party to party and through
    Sessions under the armed recorder (conftest.py has pinned the CPU
    and the compile cache already)."""
    import os
    import sys

    scripts = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts")
    sys.path.insert(0, scripts)
    try:
        import trace_check
    finally:
        sys.path.remove(scripts)
    monkeypatch.setattr(trace_check, "_setup_cpu_jax", lambda: None)
    assert trace_check.check_transcript_equality() == []


def test_closing_the_bridge_ends_its_reply_waits():
    """A request nobody answers holds a queue worker for the whole reply
    window; closing the bridge ends the wait (un-acked, so the durable
    queue keeps the request) and the fabric's workers can be joined."""
    from mpcium_tpu.consumers.signing_consumer import SigningConsumer
    from mpcium_tpu.transport.loopback import LoopbackFabric

    before = set(threading.enumerate())
    fabric = LoopbackFabric(workers=2)
    bridge = SigningConsumer(fabric.transport(), reply_timeout_s=300.0)
    bridge.run()
    fabric.enqueue(wire.TOPIC_SIGNING_REQUEST, b"{}", idempotency_key="x")
    deadline = time.monotonic() + 10
    while (bridge.metrics.gauge("bridge.inflight").value < 1
           and time.monotonic() < deadline):
        time.sleep(0.01)
    assert bridge.metrics.gauge("bridge.inflight").value == 1
    t0 = time.monotonic()
    bridge.close()
    fabric.close(join_timeout_s=10.0)
    assert time.monotonic() - t0 < 5.0
    assert bridge.metrics.gauge("bridge.inflight").value == 0
    assert bridge.metrics.gauge("bridge.inflight_peak").value == 1
    assert bridge.metrics.histogram("bridge.reply_wait_s").count == 0
    assert bridge.metrics.histogram("transport.queue_wait_s").count == 1
    assert not [t for t in set(threading.enumerate()) - before
                if t.name.startswith("loopback")]
