"""Resharing: old committee → new committee, both curves, sign-after-rotate."""
import json
import secrets
from pathlib import Path

import pytest

from mpcium_tpu.core import hostmath as hm
from mpcium_tpu.core import paillier as pl
from mpcium_tpu.protocol.base import ProtocolError
from mpcium_tpu.protocol.eddsa.keygen import EDDSAKeygenParty
from mpcium_tpu.protocol.eddsa.signing import EDDSASigningParty
from mpcium_tpu.protocol.resharing import ResharingParty
from mpcium_tpu.protocol.runner import run_protocol

DATA = Path(__file__).resolve().parent.parent / "mpcium_tpu" / "data"


@pytest.fixture(scope="module")
def ed_wallet():
    ids = ["n0", "n1", "n2"]
    parties = {
        pid: EDDSAKeygenParty("w-ed", pid, ids, threshold=1) for pid in ids
    }
    run_protocol(parties)
    return {pid: p.result for pid, p in parties.items()}


def test_eddsa_reshare_to_new_committee(ed_wallet):
    old_quorum = ["n0", "n1"]
    new_committee = ["n2", "n3", "n4", "n5"]  # fully disjoint from quorum
    t_new = 2
    pub = ed_wallet["n0"].public_key
    vss = ed_wallet["n0"].vss_commitments
    parties = {}
    for pid in old_quorum:
        parties[pid] = ResharingParty(
            "rs1", pid, "ed25519", old_quorum, new_committee, t_new,
            old_share=ed_wallet[pid],
        )
    for pid in new_committee:
        parties[pid] = ResharingParty(
            "rs1", pid, "ed25519", old_quorum, new_committee, t_new,
            old_public_key=pub, old_vss_commitments=vss,
        )
    run_protocol(parties)
    new_shares = {pid: parties[pid].result for pid in new_committee}
    assert all(s is not None for s in new_shares.values())
    assert parties["n0"].result is None  # old-only
    assert all(s.public_key == pub for s in new_shares.values())
    assert all(s.aux.get("is_reshared") for s in new_shares.values())

    # sign with t_new+1 NEW members; signature verifies under the OLD key
    quorum = ["n3", "n4", "n5"]
    msg = b"post-rotation tx"
    signers = {
        pid: EDDSASigningParty(
            "tx-rs", pid, quorum, new_shares[pid], msg
        )
        for pid in quorum
    }
    run_protocol(signers)
    sig = next(iter(signers.values())).result
    assert hm.ed25519_verify(pub, msg, sig)


def test_eddsa_reshare_overlapping_member(ed_wallet):
    """A node in both committees plays both roles in one party object."""
    old_quorum = ["n0", "n2"]
    new_committee = ["n0", "n1", "n9"]
    pub = ed_wallet["n0"].public_key
    vss = ed_wallet["n0"].vss_commitments
    parties = {}
    for pid in old_quorum:
        parties[pid] = ResharingParty(
            "rs2", pid, "ed25519", old_quorum, new_committee, 1,
            old_share=ed_wallet[pid],
            old_public_key=pub, old_vss_commitments=vss,
        )
    for pid in new_committee:
        if pid in parties:
            continue
        parties[pid] = ResharingParty(
            "rs2", pid, "ed25519", old_quorum, new_committee, 1,
            old_public_key=pub, old_vss_commitments=vss,
        )
    run_protocol(parties)
    shares = {pid: parties[pid].result for pid in new_committee}
    quorum = ["n1", "n9"]
    signers = {
        pid: EDDSASigningParty("tx-rs2", pid, quorum, shares[pid], b"hello")
        for pid in quorum
    }
    run_protocol(signers)
    assert hm.ed25519_verify(pub, b"hello", signers["n1"].result)


def test_reshare_rejects_bad_subshare(ed_wallet):
    """Tampered sub-share must be caught by the VSS check."""
    from mpcium_tpu.protocol.resharing import R2_SHARE

    old_quorum = ["n0", "n1"]
    new_committee = ["n7", "n8"]
    pub = ed_wallet["n0"].public_key
    vss = ed_wallet["n0"].vss_commitments
    parties = {}
    for pid in old_quorum:
        parties[pid] = ResharingParty(
            "rs3", pid, "ed25519", old_quorum, new_committee, 1,
            old_share=ed_wallet[pid],
        )
    for pid in new_committee:
        parties[pid] = ResharingParty(
            "rs3", pid, "ed25519", old_quorum, new_committee, 1,
            old_public_key=pub, old_vss_commitments=vss,
        )

    class TamperingRunner:
        pass

    from collections import deque

    queue = deque()
    for party in parties.values():
        for m in party.start():
            queue.append(m)
    with pytest.raises(ProtocolError, match="VSS"):
        while queue:
            msg = queue.popleft()
            if msg.round == R2_SHARE and msg.from_id == "n0":
                tampered = dict(msg.payload)
                tampered["share"] = str((int(tampered["share"]) + 1) % hm.ED_L)
                msg = type(msg)(
                    msg.session_id, msg.round, msg.from_id, tampered, msg.to
                )
            targets = (
                [p for pid, p in parties.items() if pid != msg.from_id]
                if msg.is_broadcast
                else [parties[msg.to]]
            )
            for t in targets:
                for out in t.receive(msg):
                    queue.append(out)


@pytest.fixture(scope="module")
def ecdsa_setup():
    d = json.load(open(DATA / "test_preparams.json"))["preparams"]
    preparams = {k: pl.PreParams.from_json(v) for k, v in d.items()}
    from mpcium_tpu.protocol.ecdsa.keygen import ECDSAKeygenParty

    ids = sorted(preparams)
    parties = {
        pid: ECDSAKeygenParty(
            "w-ec", pid, ids, threshold=1, preparams=preparams[pid]
        )
        for pid in ids
    }
    run_protocol(parties)
    return preparams, {pid: p.result for pid, p in parties.items()}


@pytest.mark.slow  # GG18 keygen in its fixture, then a reshare and a sign: over two minutes
def test_ecdsa_reshare_and_sign(ecdsa_setup):
    preparams, wallets = ecdsa_setup
    ids = sorted(wallets)
    old_quorum = ids[:2]
    new_committee = ids  # same 3 nodes, fresh shares
    pub = wallets[ids[0]].public_key
    vss = wallets[ids[0]].vss_commitments
    parties = {}
    for pid in ids:
        parties[pid] = ResharingParty(
            "rs-ec", pid, "secp256k1", old_quorum, new_committee, 1,
            old_share=wallets[pid] if pid in old_quorum else None,
            old_public_key=pub, old_vss_commitments=vss,
            preparams=preparams[pid],
        )
    run_protocol(parties)
    new_shares = {pid: parties[pid].result for pid in ids}
    assert all(s is not None and s.aux["is_reshared"] for s in new_shares.values())
    assert all(s.public_key == pub for s in new_shares.values())
    # old shares + new shares interpolate to the same secret
    from mpcium_tpu.protocol.ecdsa.signing import ECDSASigningParty

    digest = int.from_bytes(secrets.token_bytes(32), "big")
    quorum = [ids[1], ids[2]]
    signers = {
        pid: ECDSASigningParty("tx-ec-rs", pid, quorum, new_shares[pid], digest)
        for pid in quorum
    }
    run_protocol(signers)
    res = signers[quorum[0]].result
    assert hm.ecdsa_verify(
        hm.secp_decompress(pub), digest, res["r"], res["s"]
    )
