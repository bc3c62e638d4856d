"""Verify-drive: batched DKG -> batched signing (both curves) -> reshare
-> OpenSSL-verified signatures, over the public package surface, plus an
AEAD-encrypted broker roundtrip."""
import os

# protocol math on CPU (the chip run of the served path is chip_smoke.py)
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

from mpcium_tpu.utils import jax_cache

jax_cache.configure()

import faulthandler
import secrets
import signal
import threading
import time

faulthandler.register(signal.SIGUSR1)

from mpcium_tpu import wire
from mpcium_tpu.cluster import LocalCluster, load_test_preparams
from mpcium_tpu.engine import gg18_batch as gb

pre = load_test_preparams(bits=1024)
cluster = LocalCluster(
    n_nodes=3, threshold=1, preparams=pre, min_paillier_bits=1024,
    batch_signing=True, batch_window_s=0.2, reply_timeout_s=1800.0,
)
for ec in cluster.consumers:
    ec.scheduler.gg18_dom = gb.Domains(alpha=600, beta_prime=320, gamma_bob=600)
    ec.scheduler.manifest_timeout_s = 600.0

# ---- batched wallet creation (2 wallets in one manifest) -------------------
created = {}
done = threading.Event()
sub = cluster.client.on_wallet_creation_result(
    lambda ev: (created.__setitem__(ev.wallet_id, ev),
                len(created) == 2 and done.set())
)
cluster.client.create_wallet("vw0")
cluster.client.create_wallet("vw1")
assert done.wait(900), f"keygen incomplete: {list(created)}"
sub.unsubscribe()
for wid, ev in created.items():
    assert ev.result_type == wire.RESULT_SUCCESS, ev.error_reason
kg_batches = sum(ec.scheduler.batches_run for ec in cluster.consumers)
print(f"[1] batched keygen OK: 2 wallets, batches_run={kg_batches} (3 nodes)")

# wait until EVERY node persisted both curves' shares (on this 1-core host
# the other nodes' finalize threads can lag the first success event by the
# cold-compile time; production redelivery budgets assume real hardware)
deadline = time.time() + 1200
while time.time() < deadline:
    try:
        for node in cluster.nodes.values():
            for wid in ("vw0", "vw1"):
                node.load_share("ed25519", wid)
                node.load_share("secp256k1", wid)
        break
    except Exception:
        time.sleep(2)
else:
    raise AssertionError("shares did not persist cluster-wide")
print("[1b] all 3 nodes hold both curves' shares for both wallets")

# ---- batched signing, both curves -----------------------------------------
results = {}
sdone = threading.Event()
sub = cluster.client.on_sign_result(
    lambda ev: (results.__setitem__(ev.tx_id, ev),
                len(results) == 4 and sdone.set())
)
txs = {}
for i, wid in enumerate(("vw0", "vw1")):
    for kt in ("ed25519", "secp256k1"):
        tx = secrets.token_bytes(32)
        tid = f"vtx-{kt}-{i}"
        txs[tid] = (wid, kt, tx)
        cluster.client.sign_transaction(wire.SignTxMessage(
            key_type=kt, wallet_id=wid, network_internal_code="x",
            tx_id=tid, tx=tx,
        ))
assert sdone.wait(1800), f"signing incomplete: {list(results)}"
sub.unsubscribe()

# independent verification via OpenSSL (cryptography)
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec as _ec, utils
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey
from mpcium_tpu.core import hostmath as hm

for tid, ev in results.items():
    wid, kt, tx = txs[tid]
    assert ev.result_type == wire.RESULT_SUCCESS, f"{tid}: {ev.error_reason}"
    if kt == "ed25519":
        pub = Ed25519PublicKey.from_public_bytes(
            bytes.fromhex(created[wid].eddsa_pub_key))
        pub.verify(bytes.fromhex(ev.signature), tx)  # raises on failure
    else:
        p = hm.secp_decompress(bytes.fromhex(created[wid].ecdsa_pub_key))
        key = _ec.EllipticCurvePublicNumbers(p.x, p.y, _ec.SECP256K1()).public_key()
        key.verify(
            utils.encode_dss_signature(int(ev.r, 16), int(ev.s, 16)),
            tx, _ec.ECDSA(utils.Prehashed(hashes.SHA256())),
        )
print("[2] batched signing OK: 4 sigs (2 ed25519 + 2 GG18), OpenSSL-verified")

# ---- batched resharing -----------------------------------------------------
rres = {}
rdone = threading.Event()
sub = cluster.client.on_resharing_result(
    lambda ev: (rres.__setitem__((ev.wallet_id, ev.key_type), ev),
                len(rres) == 2 and rdone.set())
)
cluster.client.resharing("vw0", 2, "ed25519")
cluster.client.resharing("vw1", 2, "ed25519")
assert rdone.wait(900), f"reshare incomplete: {list(rres)}"
sub.unsubscribe()
for k, ev in rres.items():
    assert ev.result_type == wire.RESULT_SUCCESS, f"{k}: {ev.error_reason}"
share = cluster.nodes["node0"].load_share("ed25519", "vw0")
assert share.epoch == 1 and share.threshold == 2

# sign after rotation
ev = cluster.sign_sync(wire.SignTxMessage(
    key_type="ed25519", wallet_id="vw0", network_internal_code="x",
    tx_id="vtx-post-reshare", tx=b"\x07" * 32,
), timeout_s=900)
assert ev.result_type == wire.RESULT_SUCCESS, ev.error_reason
Ed25519PublicKey.from_public_bytes(
    bytes.fromhex(created["vw0"].eddsa_pub_key)
).verify(bytes.fromhex(ev.signature), b"\x07" * 32)
print("[3] batched reshare OK: epoch=1, t=2, post-rotation signature verifies")
cluster.close()

# ---- AEAD broker channel ---------------------------------------------------
from mpcium_tpu.transport.tcp import BrokerServer, tcp_transport

b = BrokerServer(port=0, auth_token="verify-token", encrypt=True)
t1 = tcp_transport(b.host, b.port, auth_token="verify-token", encrypt=True)
t2 = tcp_transport(b.host, b.port, auth_token="verify-token", encrypt=True)
got = []
evt = threading.Event()
t2.pubsub.subscribe("v.enc", lambda d: (got.append(d), evt.set()))
time.sleep(0.2)
t1.pubsub.publish("v.enc", b"over-the-wire")
assert evt.wait(5) and got == [b"over-the-wire"]
b.close()
print("[4] AEAD broker channel OK: encrypted pub/sub roundtrip")
print("VERIFY-DRIVE: ALL OK")
