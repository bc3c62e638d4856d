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
