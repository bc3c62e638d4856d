"""BatchedEDDSASigningParty: the distributed batched protocol, driven
transport-free (3 parties, B wallets, per-lane failure isolation)."""
import secrets

import numpy as np
import pytest

from mpcium_tpu.core import hostmath as hm
from mpcium_tpu.engine import eddsa_batch as eb
from mpcium_tpu.protocol.base import ProtocolError
from mpcium_tpu.protocol.eddsa.batch_signing import BatchedEDDSASigningParty
from mpcium_tpu.protocol.runner import run_protocol


@pytest.mark.slow  # 54 s: three parties compile the full engine
def test_three_party_batch_signs_and_verifies():
    ids = ["n0", "n1", "n2"]
    B = 5
    shares = eb.dealer_keygen_batch(B, ids, threshold=2)
    messages = [secrets.token_bytes(32) for _ in range(B)]
    parties = {
        pid: BatchedEDDSASigningParty(
            "bs-1", pid, ids, shares[i], messages
        )
        for i, pid in enumerate(ids)
    }
    run_protocol(parties)
    for pid, p in parties.items():
        ok = p.result["ok"]
        assert ok.all(), f"{pid}: {ok}"
        sigs = p.result["signatures"]
        for w in range(B):
            assert hm.ed25519_verify(
                shares[0][w].public_key, messages[w], sigs[w].tobytes()
            )


def test_commitment_fraud_aborts_with_culprit():
    ids = ["n0", "n1"]
    B = 2
    shares = eb.dealer_keygen_batch(B, ids, threshold=1)
    messages = [b"\x01" * 32, b"\x02" * 32]
    parties = {
        pid: BatchedEDDSASigningParty("bs-2", pid, ids, shares[i], messages)
        for i, pid in enumerate(ids)
    }
    # n1 equivocates: reveals a different nonce block than it committed to
    outbox = []
    for p in parties.values():
        outbox.extend(p.start())
    tampered = []
    for m in outbox:
        if m.round == "eddsa/bsign/1/commit" and m.from_id == "n1":
            pass  # commitment goes out as-is
        tampered.append(m)
    # deliver commitments
    second = []
    for m in tampered:
        for pid, p in parties.items():
            if pid != m.from_id:
                second.extend(p.receive(m))
    # corrupt n1's reveal block before delivery
    with pytest.raises(ProtocolError) as ei:
        for m in second:
            if m.round == "eddsa/bsign/2/reveal" and m.from_id == "n1":
                blk = bytearray(bytes.fromhex(m.payload["R"]))
                blk[0] ^= 1
                m.payload["R"] = bytes(blk).hex()
            for pid, p in parties.items():
                if pid != m.from_id:
                    p.receive(m)
    assert ei.value.args[-1] == "n1" or "n1" in str(ei.value)


# -- raw messages of different lengths in one batch -----------------------------

class _FixedRng:
    """A hash-counter stream where the CSPRNG would stand, so two runs
    draw the same nonces and blinds (tests/test_pipeline.py's pattern)."""

    def __init__(self, seed: bytes):
        self.seed, self.ctr = seed, 0

    def token_bytes(self, n: int) -> bytes:
        import hashlib

        out = bytearray()
        while len(out) < n:
            out += hashlib.sha256(
                self.seed + self.ctr.to_bytes(4, "little")).digest()
            self.ctr += 1
        return bytes(out[:n])


def _ragged_messages(B: int):
    """150 to 1,167 bytes (a Solana message's range), no two alike."""
    import random

    rng = random.Random(43)
    sizes = [1167, 150, 215] + [rng.randrange(150, 1168)
                                for _ in range(B - 3)]
    return [rng.randbytes(n) for n in sizes]


_IDS = ["n0", "n1", "n2"]


def _run_ragged(q: int, shares, messages, metrics=None):
    quorum = _IDS[:q]
    parties = {
        pid: BatchedEDDSASigningParty(
            "bs-ragged", pid, quorum, shares[i], messages,
            rng=_FixedRng(pid.encode()), metrics=metrics)
        for i, pid in enumerate(quorum)
    }
    run_protocol(parties)
    return parties


@pytest.mark.parametrize("q", [2, 3])
def test_a_ragged_batch_signs_raw_messages_on_the_device(q, monkeypatch):
    """Every signature verifies over its raw message under OpenSSL, the
    challenge never went to the host, and with the nonces fixed the
    bytes are those of the run that hashes a row at a time by hashlib."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey,
    )

    from mpcium_tpu.utils.metrics import MetricsRegistry

    B = 8
    messages = _ragged_messages(B)
    assert len({len(m) for m in messages}) == B
    metrics = MetricsRegistry()
    shares = eb.dealer_keygen_batch(B, _IDS, threshold=1)
    parties = _run_ragged(q, shares, messages, metrics)
    sigs = parties["n0"].result["signatures"]
    for pid, p in parties.items():
        assert p.result["ok"].all(), pid
        assert np.array_equal(p.result["signatures"], sigs)
    for w in range(B):
        Ed25519PublicKey.from_public_bytes(
            shares[0][w].public_key).verify(sigs[w].tobytes(), messages[w])
    counters = metrics.snapshot()["counters"]
    assert counters.get("party.eddsa.host_hash_rows_total", 0) == 0
    assert counters["party.eddsa.hash_blocks_total"] == q * B * 16

    monkeypatch.setenv("MPCIUM_EDDSA_DEVICE_HASH", "0")
    host_metrics = MetricsRegistry()
    host = _run_ragged(q, shares, messages, host_metrics)
    assert np.array_equal(host["n0"].result["signatures"], sigs)
    counters = host_metrics.snapshot()["counters"]
    assert counters["party.eddsa.host_hash_rows_total"] == q * B
    assert counters.get("party.eddsa.hash_blocks_total", 0) == 0


def test_a_message_past_the_top_rung_is_hashed_on_the_host_and_counted():
    from mpcium_tpu.utils.metrics import MetricsRegistry

    B = 8
    messages = _ragged_messages(B)
    messages[3] = b"\x07" * 1968
    metrics = MetricsRegistry()
    shares = eb.dealer_keygen_batch(B, _IDS, threshold=1)
    parties = _run_ragged(2, shares, messages, metrics)
    sigs = parties["n0"].result["signatures"]
    assert parties["n0"].result["ok"].all()
    for w in range(B):
        assert hm.ed25519_verify(
            shares[0][w].public_key, messages[w], sigs[w].tobytes())
    counters = metrics.snapshot()["counters"]
    assert counters["party.eddsa.host_hash_rows_total"] == 2 * B
