"""The plain reference of the batched GG18 party inside the repo's own
suite: the per-session ``protocol/ecdsa/signing.py``. Both sign the same
seeded wallets' digests at the benchmark rehearsal's size (a batch of 2,
quorum 3, 1024-bit fixtures, shrunk proof domains) and both are held to
OpenSSL: every signature verifies under its wallet's key over the digest
as it is, and the batched party's are low-s. (The two draw their own
nonces, so the signatures differ; parity is at the result level.)

Slow tier, via the subprocess wrapper of the other distributed-GG18 suites
(tests/test_gg18_batch_party.py says why)."""
import dataclasses
import os
import random

import pytest
from conftest import run_isolated
from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec, utils

pytestmark = pytest.mark.slow

_INNER = os.environ.get("MPCIUM_GG18_VS_SESSION_INNER")
IDS = ["node0", "node1", "node2"]
B = 2


def test_batched_against_per_session_isolated():
    if _INNER:
        pytest.skip("wrapper entry; inner run executes the real test")
    run_isolated(__file__, "test_both_parties_sign_what_openssl_accepts",
                 "MPCIUM_GG18_VS_SESSION_INNER")


def _openssl_accepts(pub: bytes, digest: bytes, r: int, s: int) -> bool:
    try:
        ec.EllipticCurvePublicKey.from_encoded_point(
            ec.SECP256K1(), pub).verify(
                utils.encode_dss_signature(r, s), digest,
                ec.ECDSA(utils.Prehashed(hashes.SHA256())))
    except InvalidSignature:
        return False
    return True


@pytest.mark.skipif(not _INNER, reason="runs via the subprocess wrapper")
def test_both_parties_sign_what_openssl_accepts():
    from mpcium_tpu.cluster import load_test_preparams
    from mpcium_tpu.engine import gg18_batch as gb
    from mpcium_tpu.protocol.ecdsa.batch_signing import (
        BatchedECDSASigningParty,
    )
    from mpcium_tpu.protocol.ecdsa.signing import ECDSASigningParty
    from mpcium_tpu.protocol.runner import run_protocol

    rng = random.Random(2147492829)
    shares = gb.dealer_keygen_secp_batch(
        B, IDS, threshold=1, preparams=load_test_preparams(bits=1024))
    digests = [rng.randbytes(32) for _ in range(B)]
    dom = gb.Domains(alpha=600, beta_prime=320, gamma_bob=600)

    batched = {
        pid: BatchedECDSASigningParty(
            "both-b", pid, IDS, shares[i], digests, dom=dom)
        for i, pid in enumerate(IDS)
    }
    run_protocol(batched)
    for pid, p in batched.items():
        assert p.result["ok"].all(), pid
        for w in range(B):
            r = int.from_bytes(p.result["r"][w].tobytes(), "big")
            s = int.from_bytes(p.result["s"][w].tobytes(), "big")
            assert s <= gb.Q // 2
            assert _openssl_accepts(
                shares[0][w].public_key, digests[w], r, s), (pid, w)

    # the per-session party draws from the full proof domains (β′ < q⁵),
    # which a 1024-bit N does not hold: the same key shares with the
    # committee's material from the 2048-bit fixtures
    full = gb.dealer_keygen_secp_batch(
        1, IDS, threshold=1, preparams=load_test_preparams(bits=2048))
    for w in range(B):
        parties = {
            pid: ECDSASigningParty(
                f"both-s{w}", pid, IDS,
                dataclasses.replace(shares[i][w], aux=full[i][0].aux),
                int.from_bytes(digests[w], "big"))
            for i, pid in enumerate(IDS)
        }
        run_protocol(parties)
        for pid, p in parties.items():
            sig = p.result
            r, s = int(sig["r"]), int(sig["s"])
            assert _openssl_accepts(
                shares[0][w].public_key, digests[w], r, s), (pid, w)
