"""BatchedECDSASigningParty: the distributed batched GG18 protocol,
driven transport-free (the secp256k1 analogue of
tests/test_batch_signing_party.py — 9 wire rounds, per-lane ok masks).

Runs via a subprocess wrapper (same machinery as test_batch_dkg_party):
the round-5 live-migrated host deterministically SEGFAULTs in XLA:CPU
codegen compiling the distributed-GG18-party graphs — fresh process,
MPCIUM_TESTS_NO_CACHE=1 — while the engine-level GG18 suites pass.
Isolation keeps the crash from killing the whole pytest process, and
MPCIUM_XFAIL_XLA_CRASH=1 (opt-in, known-bad hosts only) downgrades it
to xfail; everything is green where XLA:CPU is healthy."""
import os
import secrets

import pytest

from conftest import run_isolated

_INNER = os.environ.get("MPCIUM_GG18_PARTY_INNER")


@pytest.mark.slow  # the whole GG18 party compiles on XLA:CPU: over eight minutes
def test_two_party_batch_isolated():
    if _INNER:
        pytest.skip("wrapper entry; inner run executes the real test")
    run_isolated(
        __file__, "test_two_party_batch_signs_and_verifies",
        "MPCIUM_GG18_PARTY_INNER",
    )


from mpcium_tpu.core import hostmath as hm
from mpcium_tpu.engine import gg18_batch as gb
from mpcium_tpu.protocol.base import ProtocolError
from mpcium_tpu.protocol.ecdsa.batch_signing import (
    BatchedECDSASigningParty, quorum_material_digest,
)
from mpcium_tpu.protocol.runner import run_protocol

TEST_DOM = gb.Domains(alpha=600, beta_prime=320, gamma_bob=600)


@pytest.fixture(scope="module")
def small_preparams():
    from mpcium_tpu.cluster import load_test_preparams

    return load_test_preparams(bits=1024)


@pytest.mark.slow  # the wrapper above runs it
@pytest.mark.skipif(not _INNER, reason="runs via the subprocess wrapper")
def test_two_party_batch_signs_and_verifies(small_preparams):
    ids = ["node0", "node1"]
    B = 2
    shares = gb.dealer_keygen_secp_batch(
        B, ids, threshold=1, preparams=small_preparams
    )
    digests = [secrets.token_bytes(32) for _ in range(B)]
    parties = {
        pid: BatchedECDSASigningParty(
            "gbs-1", pid, ids, shares[i], digests, dom=TEST_DOM
        )
        for i, pid in enumerate(ids)
    }
    run_protocol(parties)
    for pid, p in parties.items():
        assert p.result["ok"].all(), f"{pid}: {p.result['ok']}"
        for w in range(B):
            pub = hm.secp_decompress(shares[0][w].public_key)
            r = int.from_bytes(p.result["r"][w].tobytes(), "big")
            s = int.from_bytes(p.result["s"][w].tobytes(), "big")
            d = int.from_bytes(digests[w], "big")
            assert s <= gb.Q // 2
            assert hm.ecdsa_verify(pub, d, r, s), f"{pid} wallet {w}"


def test_material_digest_agrees_across_quorum(small_preparams):
    ids = ["node0", "node1", "node2"]
    shares = gb.dealer_keygen_secp_batch(
        1, ids, threshold=1, preparams=small_preparams
    )
    digs = {quorum_material_digest(shares[i][0]) for i in range(3)}
    assert len(digs) == 1 and "" not in digs


def test_mixed_material_rejected(small_preparams):
    ids = ["node0", "node1"]
    s_a = gb.dealer_keygen_secp_batch(
        1, ids, threshold=1, preparams=small_preparams
    )
    # wallet from a different aux generation (node2's preparams as node0's)
    other = {
        "node0": small_preparams["node2"],
        "node1": small_preparams["node1"],
    }
    s_b = gb.dealer_keygen_secp_batch(1, ids, threshold=1, preparams=other)
    with pytest.raises(ProtocolError, match="mixed Paillier material"):
        BatchedECDSASigningParty(
            "gbs-mix", "node0", ids, [s_a[0][0], s_b[0][0]],
            [b"\x01" * 32, b"\x02" * 32], dom=TEST_DOM,
        )
