"""In-process development cluster — the docker-compose-equivalent dev stack.

Assembles n nodes over the loopback fabric with real identities, encrypted
share stores, registries and consumers, plus a client. This is what the
reference achieves with NATS + Consul + 3 daemon processes +
setup_identities.sh (SURVEY.md §2.1 #32); here it is one object for tests,
examples and local development. Production deployments wire the same
pieces against the TCP transport and a shared control-plane KV instead.
"""
from __future__ import annotations

import json
import tempfile
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

from . import wire
from .client.client import MPCClient
from .consumers.event_consumer import EventConsumer
from .consumers.signing_consumer import SigningConsumer, TimeoutConsumer
from .core.paillier import PreParams
from .identity.identity import IdentityStore, InitiatorKey, generate_identity
from .node.node import Node
from .registry.registry import PeerRegistry
from .store.keyinfo import KeyinfoStore
from .store.kvstore import EncryptedFileKV, MemoryKV
from .trace import arm as _trace_arm
from .trace import recorder as _trace_recorder
from .trace import snapshot_chrome as _trace_snapshot_chrome
from .transport.loopback import LoopbackFabric
from .utils import interp, log, tracing
from .utils.metrics import MetricsRegistry


class _NotMine(Exception):
    """Result event for a different operation: raising naks it back to
    the work queue (transport/api.py:20 contract) so a concurrent waiter
    can dequeue it, instead of silently ack-and-discarding another
    client's result. (Like the reference, result queues remain work
    queues — one dequeuer wins per event; unclaimed mismatches
    eventually dead-letter after max redeliveries.)"""


class SyncOps:
    """Blocking convenience wrappers over an :class:`MPCClient` at
    ``self.client`` — shared by :class:`LocalCluster` (in-process) and
    :class:`RemoteCluster` (networked broker)."""

    @staticmethod
    def _await_result(subscribe, fire, matches, timeout_s, what: str):

        done = threading.Event()
        box: list = []

        def on_ev(ev):
            if not matches(ev):
                raise _NotMine(what)
            box.append(ev)
            done.set()

        sub = subscribe(on_ev)
        try:
            fire()
            if not done.wait(timeout_s):
                raise TimeoutError(f"{what} produced no result in time")
            return box[0]
        finally:
            sub.unsubscribe()

    def create_wallet_sync(
        self, wallet_id: str, timeout_s: float = 600.0
    ) -> wire.KeygenSuccessEvent:
        # keygen results land on per-wallet topics — subscribe to OUR
        # wallet's topic so concurrent clients on one broker never
        # round-robin-steal (and after max_deliver naks, dead-letter)
        # each other's results. The matches() predicate stays as a
        # belt-and-braces check.
        ev = self._await_result(
            lambda h: self.client.on_wallet_creation_result(
                h, wallet_id=wallet_id
            ),
            lambda: self.client.create_wallet(wallet_id),
            lambda ev: ev.wallet_id == wallet_id,
            timeout_s,
            f"wallet {wallet_id!r} creation",
        )
        if ev.result_type != wire.RESULT_SUCCESS:
            raise RuntimeError(f"keygen failed: {ev.error_reason}")
        return ev

    def sign_sync(
        self, msg: wire.SignTxMessage, timeout_s: float = 600.0
    ) -> wire.SigningResultEvent:
        return self._await_result(
            lambda h: self.client.on_sign_result(h, tx_id=msg.tx_id),
            lambda: self.client.sign_transaction(msg),
            lambda ev: ev.tx_id == msg.tx_id,
            timeout_s,
            f"tx {msg.tx_id!r}",
        )

    def reshare_sync(
        self, wallet_id: str, new_threshold: int, key_type: str,
        timeout_s: float = 600.0,
    ) -> wire.ResharingSuccessEvent:
        ev = self._await_result(
            lambda h: self.client.on_resharing_result(h, wallet_id=wallet_id),
            lambda: self.client.resharing(wallet_id, new_threshold, key_type),
            lambda ev: ev.wallet_id == wallet_id and ev.key_type == key_type,
            timeout_s,
            f"wallet {wallet_id!r} resharing",
        )
        if ev.result_type != wire.RESULT_SUCCESS:
            raise RuntimeError(f"resharing failed: {ev.error_reason}")
        return ev


class LocalCluster(SyncOps):
    """n identical in-process MPC nodes + a client over loopback."""

    def __init__(
        self,
        n_nodes: int = 3,
        threshold: int = 2,
        root_dir: Optional[str] = None,
        preparams: Optional[Dict[str, PreParams]] = None,
        store_password: str = "dev-password",
        min_paillier_bits: int = 2046,
        reply_timeout_s: float = 30.0,
        transport: str = "loopback",  # "loopback" | "tcp"
        batch_signing: bool = False,
        batch_window_s: float = 0.05,
        fault_plans: Optional[Dict] = None,  # node_id|"*"|"client" → FaultPlan
        broker_standby: bool = False,  # tcp only: hot-standby broker pair
        hello_timeout_s: Optional[float] = 20.0,
        session_timeout_s: Optional[float] = None,  # EventConsumer GC knobs
        gc_interval_s: Optional[float] = None,  # (chaos drills shrink both)
        session_wal: bool = False,  # encrypted per-round WAL + crash resume
        batch_max_batch: Optional[int] = None,  # SLO batching knobs (None =
        batch_deadline_ms: Optional[int] = None,  # config defaults; see
        batch_max_queue_depth: Optional[int] = None,  # config.py batch_*)
        batch_manifest_timeout_s: Optional[float] = None,
        loopback_workers: int = 16,  # fabric pool size: the signing bridge
        # holds a queue worker per in-flight sign, so this bounds them
    ):
        from .config import init_config

        self.root = Path(root_dir or tempfile.mkdtemp(prefix="mpcium-tpu-"))
        self.node_ids = [f"node{i}" for i in range(n_nodes)]
        # flight recorder is always on for clusters: bounded per-node ring
        # buffers, merged on demand by trace_snapshot(); incident dumps land
        # under the cluster root so drills can attach them to reports
        _trace_arm(node_ids=self.node_ids,
                   dump_dir=str(self.root / "trace_incidents"))
        self.node_consumers: Dict[str, EventConsumer] = {}
        self._canary = interp.Canary(self._observe_handover_lag)
        # None overrides are skipped by init_config → config defaults apply
        init_config(path=str(self.root / "nonexistent.yaml"),
                    mpc_threshold=threshold,
                    batch_max_batch=batch_max_batch,
                    batch_deadline_ms=batch_deadline_ms,
                    batch_max_queue_depth=batch_max_queue_depth,
                    batch_manifest_timeout_s=batch_manifest_timeout_s)
        self.broker = None
        self.standby_broker = None
        if transport == "tcp":
            from .transport.tcp import BrokerServer, tcp_transport

            self.broker = BrokerServer(port=0)
            standbys = None
            if broker_standby:
                self.standby_broker = BrokerServer(
                    port=0, follow=(self.broker.host, self.broker.port)
                )
                assert self.standby_broker._rep_synced.wait(10), (
                    "standby broker never synced to primary"
                )
                standbys = [(self.standby_broker.host,
                             self.standby_broker.port)]
            self._mk_transport = lambda: tcp_transport(
                self.broker.host, self.broker.port, standbys=standbys
            )
            self.fabric = None
        else:
            self.fabric = LoopbackFabric(workers=loopback_workers)
            self._mk_transport = self.fabric.transport
        # fault-injection seam (mpcium_tpu/faults): nodes with a plan get
        # their transport wrapped; with no plan nothing is constructed and
        # behavior is byte-identical to a bare cluster
        self._fault_plans = fault_plans or {}
        self.fault_transports: Dict[str, object] = {}
        self._retired_fault_transports: List[object] = []
        self._hello_timeout_s = hello_timeout_s
        self.control_kv = MemoryKV()  # the Consul analogue

        # identities (setup_identities.sh equivalent)
        ident_dir = self.root / "identity"
        for nid in self.node_ids:
            generate_identity(nid, ident_dir)
        self.initiator = InitiatorKey.generate()

        # per-node ctor state, retained so respawn_node() can rebuild a
        # killed node's runtime stack over its surviving on-disk state
        self._ident_dir = ident_dir
        self._peers = {nid: nid for nid in self.node_ids}
        self._store_password = store_password
        self._min_paillier_bits = min_paillier_bits
        self._preparams = preparams or {}
        self._session_wal = session_wal
        self._batch_signing = batch_signing
        self._batch_window_s = batch_window_s
        self._reply_timeout_s = reply_timeout_s
        self._ec_kw: Dict[str, float] = {}
        if session_timeout_s is not None:
            self._ec_kw["session_timeout_s"] = session_timeout_s
        if gc_interval_s is not None:
            self._ec_kw["gc_interval_s"] = gc_interval_s

        self.nodes: Dict[str, Node] = {}
        self.consumers: List[EventConsumer] = []
        self.signing_consumers: List[SigningConsumer] = []
        self.node_signing: Dict[str, SigningConsumer] = {}
        for nid in self.node_ids:
            self._spawn_node(nid)
        for node in self.nodes.values():
            assert node.registry.wait_all_ready(10), "cluster failed to form"
        log.info("local cluster ready", nodes=n_nodes, threshold=threshold)
        self.client = MPCClient(
            self._wrap_faults("client", self._mk_transport()), self.initiator
        )

    def _spawn_node(self, nid: str) -> EventConsumer:
        """Build one node's full runtime stack — identity, encrypted share
        store (at its canonical on-disk path), optional session-WAL store,
        registry, transport, Node, consumers — exactly the daemon boot
        sequence. Used at cluster construction and by :meth:`respawn_node`."""
        identity = IdentityStore(
            self._ident_dir, nid, self._peers,
            initiator_pubkey=self.initiator.public_bytes,
        )
        metrics = MetricsRegistry()  # the node's: its store's books too
        kv = EncryptedFileKV(self.root / "db" / nid, self._store_password,
                             metrics=metrics)
        wal = None
        if self._session_wal:
            from .store.session_wal import SessionWALStore

            wal = SessionWALStore(kv)
        registry = PeerRegistry(
            nid, self.node_ids, self.control_kv, poll_interval_s=0.05,
            metrics=metrics,
        )
        transport = self._wrap_faults(nid, self._mk_transport())
        node = Node(
            node_id=nid,
            peer_ids=self.node_ids,
            transport=transport,
            identity=identity,
            kvstore=kv,
            keyinfo=KeyinfoStore(self.control_kv),
            registry=registry,
            preparams=self._preparams.get(nid),
            min_paillier_bits=self._min_paillier_bits,
            hello_timeout_s=self._hello_timeout_s,
            session_wal=wal,
        )
        self.nodes[nid] = node
        ec = EventConsumer(
            node, transport,
            batch_signing=self._batch_signing,
            batch_window_s=self._batch_window_s,
            metrics=metrics,
            **self._ec_kw,
        )
        ec.run()
        self.consumers.append(ec)
        self.node_consumers[nid] = ec
        sc = SigningConsumer(transport, reply_timeout_s=self._reply_timeout_s,
                             metrics=ec.metrics)
        sc.run()
        self.signing_consumers.append(sc)
        self.node_signing[nid] = sc
        TimeoutConsumer(transport).run()
        registry.ready()
        return ec

    def stop_node(self, node_id: str) -> None:
        """``node_id`` leaves the way a daemon does on SIGTERM
        (node/daemon.py ``run_node``), in that order: its signing bridge
        closed (it takes no more from the durable queue; what it held
        un-acked is redelivered), its event consumer closed, its ready
        key resigned (peers drop it from their quorums at their next
        poll), its transport closed. Its sealed share store is closed and
        stays on disk, unread, for :meth:`respawn_node`. The node keeps
        its entries in ``nodes``, ``node_consumers`` and
        :meth:`metrics_snapshot` (its counters simply stop); a second
        call and the later :meth:`close` find nothing left to do."""
        node = self.nodes[node_id]
        steps = (
            ("signing_s", self.node_signing[node_id].close),
            ("consumer_s", self.node_consumers[node_id].close),
            ("resign_s", node.registry.resign),
            ("transport_s", lambda: _close_transport(node.transport)),
        )
        t0_ns = tracing.now_ns()
        took = {}
        for name, step in steps:
            t0 = time.perf_counter()
            step()
            took[name] = time.perf_counter() - t0
        node.kvstore.close()
        tracing.emit("cluster:stop_node", t0_ns, tracing.now_ns(),
                     node=node_id, **took)
        log.info("node stopped", node=node_id)

    def respawn_node(self, node_id: str) -> EventConsumer:
        """In-process restart, after a SIGKILL or after :meth:`stop_node`:
        rebuild ``node_id``'s entire runtime over its surviving on-disk
        state (identity keys, encrypted share store, session WALs) the way
        a fresh daemon boot would, then replay incomplete WAL sessions.
        The dead incarnation's objects are deliberately left in place — a
        killed process never cleans up; its crashed transport keeps
        black-holing whatever still reaches it."""
        old_ft = self.fault_transports.pop(node_id, None)
        if old_ft is not None:
            self._retired_fault_transports.append(old_ft)
        ec = self._spawn_node(node_id)
        # boot-time crash recovery, after ready() — mirrors daemon.run_node
        ec.resume_incomplete()
        return ec

    def health(self) -> Dict[str, dict]:
        """Per-node operational snapshots (EventConsumer.health): live
        sessions, dedup claims, and every scheduler metric — lane queue
        depths, shed counters, fill ratios, latency percentiles."""
        self._fold_process_stats()
        return {nid: ec.health() for nid, ec in self.node_consumers.items()}

    def _observe_handover_lag(self, lag_s: float) -> None:
        if self.node_consumers:
            next(iter(self.node_consumers.values())).metrics.histogram(
                "interp.handover_lag_s").observe(lag_s)

    def _fold_process_stats(self) -> None:
        """Bring what no node owns up to now, in the FIRST node's
        registry only (the one process and the one fabric stand behind
        every node, and a sum over the nodes counts them once): the
        interpreter account (``interp.cpu_s.<role>``,
        ``interp.threads.<role>``), the log handler's totals, and the
        loopback fabric's ``transport.dedup_hits`` / ``transport.dedup_keys``
        / ``transport.subscriptions`` (nothing of the fabric over TCP: the
        broker is another process and keeps its own)."""
        if not self.node_consumers:
            return
        stats = (self.fabric.stats() if self.fabric is not None
                 else {"counters": {}, "gauges": {}})
        next(iter(self.node_consumers.values())).metrics.fold(
            counters={**stats["counters"], **log.totals()},
            gauges={**stats["gauges"], **interp.gauges()})

    def metrics_snapshot(self) -> Dict[str, dict]:
        """Just the metric registries, keyed by node id (the soak harness
        and smoke tests consume this). ``trace.dropped_spans`` is brought
        up to date first: each node's own ring, and on the first node also
        the rings no node owns (``engine``, ``client``, ``local``), so the
        sum over the snapshot counts every ring once. The loopback
        fabric, the process's threads and its log handler, which no node
        owns either, are counted the same way
        (:meth:`_fold_process_stats`)."""
        dropped = _trace_recorder.dropped_totals()
        shared = sum(d for ring, d in dropped.items()
                     if ring not in self.node_consumers)
        for nid, ec in self.node_consumers.items():
            ec.metrics.gauge("trace.dropped_spans").set(
                float(dropped.get(nid, 0) + shared))
            shared = 0
        self._fold_process_stats()
        return {
            nid: ec.metrics.snapshot()
            for nid, ec in self.node_consumers.items()
        }

    def trace_snapshot(self, clear: bool = False,
                       meta: Optional[dict] = None) -> dict:
        """Merge every node's flight-recorder ring buffer (plus the shared
        engine/client tracks) into one Chrome-trace-event JSON document —
        pid = node, tid = session/lane — loadable in Perfetto / chrome://
        tracing. Buffers survive :meth:`close`, so drills can snapshot
        after teardown."""
        return _trace_snapshot_chrome(clear=clear, meta=meta)

    def prometheus_text(self) -> str:
        """Prometheus text exposition for the whole cluster: each node's
        registry rendered with a ``node`` label, concatenated."""
        return "".join(
            ec.metrics.to_prometheus(labels={"node": nid})
            for nid, ec in self.node_consumers.items()
        )

    def _wrap_faults(self, owner: str, transport):
        """Wrap ``transport`` in a FaultyTransport when a fault plan is
        installed for ``owner`` (or under the "*" wildcard). No plan ⇒
        the bare transport passes through untouched."""
        plan = self._fault_plans.get(owner) or (
            self._fault_plans.get("*") if owner != "client" else None
        )
        if plan is None:
            return transport
        from .faults.transport import FaultyTransport

        ft = FaultyTransport(transport, owner, plan)
        self.fault_transports[owner] = ft
        return ft

    def close(self) -> None:
        self._canary.close()
        for ec in self.consumers:
            try:
                ec.close()
            except Exception as e:  # noqa: BLE001 — dead incarnations may
                log.warn("consumer close failed", error=repr(e))  # throw
        for sc in self.signing_consumers:
            sc.close()
        for node in self.nodes.values():
            node.registry.resign()
            node.kvstore.close()
        for ft in list(self.fault_transports.values()) + \
                self._retired_fault_transports:
            ft.close()
        if self.fabric is not None:
            self.fabric.close()
        if self.broker is not None:
            self.broker.close()
        if self.standby_broker is not None:
            self.standby_broker.close()


def _close_transport(transport) -> None:
    """A node's own connection: the TCP bundle's ``client``. A loopback
    bundle is a view of the cluster's one fabric and holds nothing once
    the node's consumers have unsubscribed."""
    client = getattr(transport, "client", None)
    if client is not None:
        client.close()


class RemoteCluster(SyncOps):
    """Client-side handle to an ALREADY RUNNING networked deployment
    (broker + daemons — the docker-compose topology): the analogue of the
    reference examples connecting to a live NATS+Consul stack
    (INSTALLATION.md "Start Mpcium Nodes"; examples/generate/main.go).

    Reads broker endpoint/auth/encryption from the same config file the
    daemons use and loads the initiator's PRIVATE key (default:
    ``event_initiator.key`` next to the config, the client.go:64-146
    layout)."""

    def __init__(
        self,
        config_path: str,
        initiator_key_path: Optional[str] = None,
        passphrase: Optional[str] = None,
    ):
        from .config import init_config
        from .transport.tcp import parse_addrs, tcp_transport

        cfg = init_config(path=str(config_path))
        key_path = Path(
            initiator_key_path
            or Path(config_path).resolve().parent / "event_initiator.key"
        )
        # load the key BEFORE connecting: a missing/locked key must not
        # leak a live authenticated broker connection + reader thread
        initiator = InitiatorKey.load(key_path, passphrase)
        self.transport = tcp_transport(
            cfg.broker_host,
            cfg.broker_port,
            auth_token=cfg.broker_token or None,
            encrypt=cfg.broker_encrypt,
            standbys=parse_addrs(cfg.broker_standbys) or None,
        )
        self.client = MPCClient(self.transport, initiator)

    def close(self) -> None:
        self.transport.client.close()


def load_test_preparams(bits: int = 2048) -> Dict[str, PreParams]:
    """The committed fixtures (TEST/BENCH ONLY — production nodes generate
    fresh pre-params, reference node.go:69). ``bits=1024`` selects the
    shrunk-key fixture used by fast unit tests: FIXED keys also keep the
    persistent XLA compile cache valid across runs (fresh random moduli
    would embed different constants into every kernel)."""
    name = "test_preparams.json" if bits == 2048 else f"test_preparams_{bits}.json"
    data_path = Path(__file__).resolve().parent / "data" / name
    d = json.load(open(data_path))["preparams"]
    return {k: PreParams.from_json(v) for k, v in d.items()}
