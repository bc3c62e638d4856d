"""Node daemon: the `mpcium start -n node0` equivalent (cmd/mpcium/main.go).

Wires every subsystem by hand like the reference (main.go:86-200): config →
logging → control-plane KV → encrypted share store → keyinfo → identity →
TCP bus transport → registry → node (pre-params) → event consumer +
timeout consumer → ready → signing consumer, then blocks until
SIGINT/SIGTERM.
"""
from __future__ import annotations

import getpass
import json
import signal
import threading
import time
from pathlib import Path

from ..config import check_required, init_config
from ..consumers.event_consumer import EventConsumer
from ..consumers.signing_consumer import SigningConsumer, TimeoutConsumer
from ..identity.identity import IdentityStore
from ..registry.registry import PeerRegistry
from ..store.keyinfo import KeyinfoStore
from ..store.kvstore import EncryptedFileKV, FileKV
from ..trace import arm as trace_arm
from ..transport.tcp import tcp_transport
from ..utils import interp, log
from ..utils.metrics import MetricsRegistry
from .node import Node


def publish_health(consumer, control_kv, name: str) -> dict:
    """One health beat: publish the consumer's operational snapshot as
    JSON under ``health/<name>`` and the same registry as Prometheus text
    exposition under ``health/<name>.prom`` — so ``kv get health/node0``
    stays the whole monitoring story and a scrape sidecar can serve
    ``.prom`` verbatim. The process's interpreter account and log totals
    (``interp.*``, ``log.*``) are brought up to date in the consumer's
    registry first. Returns the JSON snapshot (tests assert on it)."""
    consumer.metrics.fold(counters=log.totals(), gauges=interp.gauges())
    snap = consumer.health()
    snap["ts"] = time.time()
    control_kv.put(
        f"health/{name}",
        json.dumps(snap, sort_keys=True).encode(),
    )
    control_kv.put(
        f"health/{name}.prom",
        consumer.metrics.to_prometheus(labels={"node": name}).encode(),
    )
    return snap


def health_loop(consumer, control_kv, name: str, stop: threading.Event,
                interval_s: float = 10.0) -> None:
    """Periodic health publisher (daemon thread body). A failed publish
    is logged and the beat continues — monitoring must never kill the
    node it monitors."""
    while not stop.wait(interval_s):
        try:
            publish_health(consumer, control_kv, name)
        except Exception as e:  # noqa: BLE001 — never kill the beat
            log.warn("health publish failed", node=name, error=repr(e))


def load_peers(cfg, kv=None) -> dict:
    """peers.json {name: uuid} (reference generate-peers.go), else the
    control-plane ``mpc_peers/`` prefix (reference LoadPeersFromConsul,
    main.go:302-311) — from ``kv`` when given (broker control plane),
    else the FileKV directory."""
    p = Path(cfg.peers_file)
    if p.exists():
        return json.loads(p.read_text())
    kv = kv if kv is not None else FileKV(cfg.control_kv_dir)
    peers = {}
    for key in kv.keys("mpc_peers/"):
        peers[key[len("mpc_peers/"):]] = (kv.get(key) or b"").decode()
    if not peers:
        raise SystemExit(
            f"no peers: neither {cfg.peers_file} nor mpc_peers/ in the "
            f"{cfg.control_plane!r} control plane (run mpcium-tpu-cli "
            f"generate-peers + register-peers first)"
        )
    return peers


def run_node(
    name: str,
    config_path: str = "config.yaml",
    decrypt_private_key: bool = False,
    debug: bool = False,
    block: bool = True,
    fault_plan=None,  # faults.FaultPlan | path to a plan JSON | None
):
    cfg = init_config(config_path)
    log.init(
        production=cfg.environment == "production",
        level="DEBUG" if debug else "INFO",
    )
    check_required(cfg, ["badger_password", "event_initiator_pubkey"])
    # arm the flight recorder for this node: bounded ring buffer, incident
    # dumps (shed / timeout / drill failure) land under the db dir
    trace_arm(node_ids=[name],
              dump_dir=str(Path(cfg.db_dir) / name / "trace_incidents"))
    metrics = MetricsRegistry()  # the node's: its store's books too
    canary = interp.Canary(metrics.histogram("interp.handover_lag_s").observe)
    # compile ledger: this node is alive but cold until boot completes —
    # health publishes state=warming so a restart paying the compile
    # wall is distinguishable from a dead node. The ledger file lands
    # beside the node's stores.
    from ..perf import compile_watch

    compile_watch.mark_warming()
    compile_watch.set_ledger_dir(str(Path(cfg.db_dir) / name))
    passphrase = cfg.passphrase or None
    if decrypt_private_key and passphrase is None:
        passphrase = getpass.getpass(f"passphrase for {name} identity key: ")

    # transport first: with the broker control plane the SAME connection
    # serves registry/keyinfo/peers (reference topology: NATS + Consul are
    # two services; here the broker is the single network rendezvous)
    from ..transport.tcp import parse_addrs

    transport = tcp_transport(
        cfg.broker_host, cfg.broker_port,
        auth_token=cfg.broker_token or None,
        encrypt=cfg.broker_encrypt,
        standbys=parse_addrs(cfg.broker_standbys),
    )
    # chaos seam (ISSUE 3): an explicit plan argument or the
    # chaos_fault_plan config knob (path to a plan JSON) wraps this
    # daemon's transport in a FaultyTransport. Absent both — the normal
    # case — nothing is constructed and the bare transport flows on.
    fault_plan = fault_plan or (cfg.chaos_fault_plan or None)
    if fault_plan is not None:
        from ..faults.plan import FaultPlan
        from ..faults.transport import FaultyTransport

        if isinstance(fault_plan, (str, Path)):
            fault_plan = FaultPlan.from_json(Path(fault_plan).read_text())
        transport = FaultyTransport(transport, name, fault_plan)
        # mpclint: disable=MPL101,MPF701 — fault-plan seed is the chaos replay handle and must be logged; not key material
        log.warn("CHAOS: fault plan installed", node=name,
                 seed=fault_plan.seed, rules=fault_plan.describe())
    if cfg.control_plane == "broker":
        from ..store.broker_kv import BrokerKV

        control_kv = BrokerKV(transport.client)
    elif cfg.control_plane == "file":
        control_kv = FileKV(cfg.control_kv_dir)
    else:
        raise SystemExit(
            f"control_plane={cfg.control_plane!r}: expected 'file' or "
            f"'broker'"
        )

    peers = load_peers(cfg, control_kv)
    if name not in peers:
        raise SystemExit(f"node {name!r} not in peer set {sorted(peers)}")

    share_store = EncryptedFileKV(Path(cfg.db_dir) / name, cfg.badger_password,
                                  metrics=metrics)
    # crash-recovery WAL (default off): journals live sessions under the
    # share store's AEAD so a SIGKILL'd node resumes mid-round after restart
    session_wal = None
    if cfg.session_wal:
        from ..store.session_wal import SessionWALStore

        session_wal = SessionWALStore(share_store)
    keyinfo = KeyinfoStore(control_kv)
    identity = IdentityStore(
        cfg.identity_dir,
        name,
        peers,
        initiator_pubkey=bytes.fromhex(cfg.event_initiator_pubkey),
        passphrase=passphrase,
    )
    registry = PeerRegistry(name, list(peers), control_kv, metrics=metrics)
    node = Node(
        node_id=name,
        peer_ids=list(peers),
        transport=transport,
        identity=identity,
        kvstore=share_store,
        keyinfo=keyinfo,
        registry=registry,
        safe_prime_pool=cfg.safe_prime_pool or None,
        session_wal=session_wal,
    )
    # multi-device hosts shard the session axis of batched dispatches
    # over every local chip (engine/sharded.py; no-op on one device). A
    # node that cannot shard over the chips it sees does not start.
    from ..engine.sharded import arm_session_axis

    mesh = arm_session_axis()
    if mesh is not None:
        log.info("session axis sharded over local devices",
                 devices=mesh.devices.size)

    consumer = EventConsumer(
        node, transport,
        batch_signing=cfg.batch_signing,
        batch_window_s=cfg.batch_window_s,
        metrics=metrics,
    )
    consumer.run()
    TimeoutConsumer(transport).run()
    registry.ready()
    # boot-time crash recovery: replay incomplete WAL sessions AFTER the
    # consumer subscribed (resumed peers' answers must not race our subs)
    # and after ready() so peers treat us as live again
    if session_wal is not None:
        try:
            consumer.resume_incomplete()
        except Exception as e:  # noqa: BLE001 — recovery must never block boot
            log.warn("WAL resume scan failed", node=name, error=repr(e))
    signing = SigningConsumer(transport, metrics=consumer.metrics)
    signing.run()
    # health surface: periodically publish the consumer's operational
    # snapshot (live sessions, dedup claims, scheduler lane depths, shed
    # counters, latency percentiles) to the control plane under
    # ``health/<name>`` — the same KV operators already watch for peer
    # liveness, so `kv get health/node0` is the whole monitoring story
    health_stop = threading.Event()
    threading.Thread(
        target=health_loop, args=(consumer, control_kv, name, health_stop),
        name=f"health-{name}", daemon=True,
    ).start()
    # every subsystem is wired and subscribed. With warm_enabled the
    # warm-start pass now pre-compiles the serving set (knobs × buckets
    # read from COMPILE_SURFACE.json) while health still publishes
    # state=warming — the node advertises ready only once the manifest
    # is covered or warm_budget_s expires. Cold boot (warm_enabled
    # false) flips straight to ready and live traffic pays the wall.
    if cfg.warm_enabled:
        from ..warm.prewarm import prewarm_for_daemon

        prewarm_for_daemon(cfg, name)
    compile_watch.mark_ready()
    log.info("node running", node=name, broker=f"{cfg.broker_host}:{cfg.broker_port}")

    if not block:
        return node, consumer, signing, registry

    stop = threading.Event()

    def _sig(_signum, _frame):
        stop.set()

    signal.signal(signal.SIGINT, _sig)
    signal.signal(signal.SIGTERM, _sig)
    stop.wait()
    log.info("shutting down", node=name)
    health_stop.set()
    canary.close()
    signing.close()
    consumer.close()
    registry.resign()
    transport.client.close()
    return 0


def run_broker(
    host: str = "127.0.0.1",
    port: int = 4333,
    block: bool = True,
    journal: str = "",
    token: str = "",
    encrypt: bool = False,
    follow: str = "",
):
    """The `nats-server` analogue: `mpcium-tpu broker`. CLI flags win;
    otherwise config.yaml's broker_journal/broker_token apply. ``follow``
    ("host:port") starts this broker as a hot standby mirroring that
    primary's queue state until the primary dies."""
    from ..config import init_config
    from ..transport.tcp import BrokerServer, parse_addrs

    cfg = init_config()
    broker = BrokerServer(
        host=host, port=port,
        journal_path=journal or cfg.broker_journal or None,
        auth_token=token or cfg.broker_token or None,
        encrypt=encrypt or cfg.broker_encrypt,
        follow=parse_addrs(follow)[0] if follow else None,
    )
    log.init()
    log.info("broker listening", host=broker.host, port=broker.port)
    if not block:
        return broker
    stop = threading.Event()
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    stop.wait()
    broker.close()
    return 0
