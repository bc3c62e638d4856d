"""Networked end-to-end: broker + 3 daemon PROCESSES over localhost TCP.

The automated analogue of the reference's INSTALLATION.md flow ("Start
Mpcium Nodes": nats-server + consul + three `mpcium start -n node<i>`
terminals + examples/ as the initiator). Everything the docker-compose
stack deploys is exercised for real here: the ops CLI bootstraps
peers/identities/initiator, `mpcium-tpu broker` and three
`mpcium-tpu start` processes are launched via subprocess, and the client
SDK drives generate → sign (both curves) → reshare → sign over the
authenticated, AEAD-encrypted TCP bus.
"""
from __future__ import annotations

import hashlib
import json
import os
import secrets
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow

from mpcium_tpu import wire
from mpcium_tpu.client.client import MPCClient
from mpcium_tpu.core import hostmath as hm
from mpcium_tpu.identity.identity import InitiatorKey
from mpcium_tpu.store.kvstore import FileKV
from mpcium_tpu.transport.tcp import tcp_transport

REPO = Path(__file__).resolve().parent.parent
TOKEN = "e2e-shared-token"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_env() -> dict:
    """Daemon/broker env: pinned to the CPU backend — several processes
    must not claim one chip (and the per-session protocol path is host
    arithmetic anyway)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MPCIUM_BROKER_TOKEN"] = TOKEN
    env["PYTHONPATH"] = ":".join(
        [str(REPO)]  # children run from the workspace cwd
        + [p for p in env.get("PYTHONPATH", "").split(":")
           if p and p != str(REPO)]
    )
    env.pop("PYTHONSTARTUP", None)
    return env


def _run_cli(module: str, *args: str, cwd: Path) -> None:
    subprocess.run(
        [sys.executable, "-m", module, *args],
        cwd=cwd, env=_child_env(), check=True, capture_output=True,
    )


@pytest.fixture(scope="module")
def stack(tmp_path_factory):
    """Bootstrap a workspace with the real ops CLI, then launch the broker
    and three node daemons as separate processes."""
    ws = tmp_path_factory.mktemp("e2e")
    port = _free_port()

    # --- ops bootstrap, exactly as scripts/setup_identities.sh does ------
    _run_cli("mpcium_tpu.cli.ops", "generate-peers", "-n", "3", cwd=ws)
    _run_cli("mpcium_tpu.cli.ops", "register-peers",
             "--registry-dir", "control", cwd=ws)
    for i in range(3):
        _run_cli("mpcium_tpu.cli.ops", "generate-identity",
                 "--node", f"node{i}", cwd=ws)
    _run_cli("mpcium_tpu.cli.ops", "generate-initiator", cwd=ws)
    initiator_pub = json.loads(
        (ws / "event_initiator.json").read_text()
    )["public_key"]

    # committed safe-prime pool (copy: pool_take consumes entries) so the
    # daemons' startup pre-params take seconds, not minutes
    pool = ws / "safeprimes.json"
    pool.write_bytes(
        (REPO / "mpcium_tpu/data/safeprimes_1024.json").read_bytes()
    )

    (ws / "config.yaml").write_text(
        "\n".join(
            [
                "environment: development",
                "mpc_threshold: 1",  # t=1 ⇒ 2-of-3 quorums (cluster.py:52)
                f'event_initiator_pubkey: "{initiator_pub}"',
                "badger_password: e2e-badger-password",
                f"broker_port: {port}",
                "broker_encrypt: true",
                f"safe_prime_pool: {pool}",
            ]
        )
    )

    procs: list = []
    logs = {}

    def _spawn(tag: str, *args: str) -> None:
        logs[tag] = open(ws / f"{tag}.log", "wb")
        procs.append(
            subprocess.Popen(
                [sys.executable, "-m", "mpcium_tpu.cli.main", *args],
                cwd=ws, env=_child_env(),
                stdout=logs[tag], stderr=subprocess.STDOUT,
            )
        )

    _spawn("broker", "broker", "--port", str(port),
           "--journal", str(ws / "queue.jsonl"), "--encrypt")
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), timeout=1).close()
            break
        except OSError:
            time.sleep(0.2)
    else:
        raise RuntimeError("broker never opened its port")

    for i in range(3):
        _spawn(f"node{i}", "start", "-n", f"node{i}")

    # readiness: the daemons announce in the shared control-plane KV
    kv = FileKV(ws / "control")
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        if len(kv.keys("ready/")) == 3:
            break
        dead = [p for p in procs if p.poll() is not None]
        if dead:
            raise RuntimeError(
                "process died during startup: "
                + (ws / "broker.log").read_text()[-2000:]
                + "".join(
                    (ws / f"node{i}.log").read_text()[-2000:] for i in range(3)
                )
            )
        time.sleep(0.5)
    else:
        raise RuntimeError("daemons never became ready")

    transport = tcp_transport("127.0.0.1", port, auth_token=TOKEN, encrypt=True)
    client = MPCClient(transport, InitiatorKey.load(ws / "event_initiator.key"))
    yield ws, client

    transport.client.close()
    for p in procs:
        p.send_signal(signal.SIGTERM)
    for p in procs:
        try:
            p.wait(timeout=15)
        except subprocess.TimeoutExpired:
            p.kill()
    for f in logs.values():
        f.close()


def _await(subscribe, fire, matches, timeout_s: float):
    import threading

    done = threading.Event()
    box: list = []

    def on_ev(ev):
        if matches(ev):
            box.append(ev)
            done.set()

    sub = subscribe(on_ev)
    try:
        fire()
        assert done.wait(timeout_s), "no result within timeout"
        return box[0]
    finally:
        sub.unsubscribe()


@pytest.fixture(scope="module")
def wallet(stack):
    _, client = stack
    # "cluster not ready" is retryable (a starved host can let 1 Hz
    # registry heartbeats go stale for a beat) — retry like a real
    # initiator would; any other failure is terminal
    for attempt in range(5):
        ev = _await(
            client.on_wallet_creation_result,
            lambda: client.create_wallet(f"w-e2e-{attempt}"),
            lambda ev, a=attempt: ev.wallet_id == f"w-e2e-{a}",
            timeout_s=600,
        )
        if ev.result_type == wire.RESULT_SUCCESS:
            return ev
        assert "not ready" in ev.error_reason, ev.error_reason
        time.sleep(3)
    raise AssertionError(f"wallet creation kept failing: {ev.error_reason}")


def test_create_wallet(wallet):
    assert not hm.secp_decompress(bytes.fromhex(wallet.ecdsa_pub_key)).is_infinity
    hm.ed_decompress(bytes.fromhex(wallet.eddsa_pub_key))


def test_sign_eddsa(stack, wallet):
    _, client = stack
    tx = b"e2e solana transfer"
    ev = _await(
        client.on_sign_result,
        lambda: client.sign_transaction(
            wire.SignTxMessage(
                key_type="ed25519", wallet_id=wallet.wallet_id,
                network_internal_code="solana-devnet",
                tx_id="tx-e2e-ed", tx=tx,
            )
        ),
        lambda ev: ev.tx_id == "tx-e2e-ed",
        timeout_s=300,
    )
    assert ev.result_type == wire.RESULT_SUCCESS, ev.error_reason
    assert hm.ed25519_verify(
        bytes.fromhex(wallet.eddsa_pub_key), tx, bytes.fromhex(ev.signature)
    )


def test_sign_ecdsa(stack, wallet):
    _, client = stack
    digest = hashlib.sha256(b"e2e eth transfer").digest()
    ev = _await(
        client.on_sign_result,
        lambda: client.sign_transaction(
            wire.SignTxMessage(
                key_type="secp256k1", wallet_id=wallet.wallet_id,
                network_internal_code="ethereum",
                tx_id="tx-e2e-ec", tx=digest,
            )
        ),
        lambda ev: ev.tx_id == "tx-e2e-ec",
        timeout_s=300,
    )
    assert ev.result_type == wire.RESULT_SUCCESS, ev.error_reason
    assert hm.ecdsa_verify(
        hm.secp_decompress(bytes.fromhex(wallet.ecdsa_pub_key)),
        int.from_bytes(digest, "big"), int(ev.r, 16), int(ev.s, 16),
    )


def test_reshare_then_sign(stack, wallet):
    _, client = stack
    ev = _await(
        client.on_resharing_result,
        lambda: client.resharing(wallet.wallet_id, new_threshold=2, key_type="ed25519"),
        lambda ev: ev.wallet_id == wallet.wallet_id,
        timeout_s=600,
    )
    assert ev.result_type == wire.RESULT_SUCCESS, ev.error_reason

    tx = secrets.token_bytes(24)
    sev = _await(
        client.on_sign_result,
        lambda: client.sign_transaction(
            wire.SignTxMessage(
                key_type="ed25519", wallet_id=wallet.wallet_id,
                network_internal_code="solana-devnet",
                tx_id="tx-e2e-post-reshare", tx=tx,
            )
        ),
        lambda ev: ev.tx_id == "tx-e2e-post-reshare",
        timeout_s=300,
    )
    assert sev.result_type == wire.RESULT_SUCCESS, sev.error_reason
    assert hm.ed25519_verify(
        bytes.fromhex(ev.pub_key or wallet.eddsa_pub_key), tx,
        bytes.fromhex(sev.signature),
    )


def test_example_networked_mode(stack, wallet):
    """examples/generate.py --config drives the SAME running deployment
    (RemoteCluster): the reference examples' mode against a live stack."""
    ws, _ = stack
    r = subprocess.run(
        [
            sys.executable, str(REPO / "examples" / "generate.py"),
            "--config", str(ws / "config.yaml"),
            "wallet-example-net",
        ],
        env=_child_env(), cwd=ws, capture_output=True, text=True,
        timeout=600,
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "wallet created: wallet-example-net" in r.stdout
