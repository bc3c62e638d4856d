"""GG18 ECDSA: ZK proofs, MtA, keygen + signing end-to-end."""
import json
import secrets
from pathlib import Path

import pytest

from mpcium_tpu.core import hostmath as hm
from mpcium_tpu.core import paillier as pl
from mpcium_tpu.protocol.ecdsa import mta, zk
from mpcium_tpu.protocol.ecdsa.keygen import ECDSAKeygenParty
from mpcium_tpu.protocol.ecdsa.signing import ECDSASigningParty
from mpcium_tpu.protocol.runner import run_protocol

DATA = Path(__file__).resolve().parent.parent / "mpcium_tpu" / "data"


@pytest.fixture(scope="module")
def preparams():
    d = json.load(open(DATA / "test_preparams.json"))["preparams"]
    return {k: pl.PreParams.from_json(v) for k, v in d.items()}


@pytest.fixture(scope="module")
def wallets(preparams):
    """One DKG run shared by the signing tests."""
    ids = sorted(preparams)
    parties = {
        pid: ECDSAKeygenParty("w1", pid, ids, threshold=1, preparams=preparams[pid])
        for pid in ids
    }
    run_protocol(parties)
    return {pid: p.result for pid, p in parties.items()}


def test_dln_proof(preparams):
    pp = preparams["node0"]
    pq = (pp.P - 1) // 2 * ((pp.Q - 1) // 2)
    proof = zk.DLNProof.prove(pp.h1, pp.h2, pp.alpha, pq, pp.NTilde)
    assert proof.verify(pp.h1, pp.h2, pp.NTilde)
    assert not proof.verify(pp.h2, pp.h1, pp.NTilde)  # wrong statement
    rt = zk.DLNProof.from_json(proof.to_json())
    assert rt.verify(pp.h1, pp.h2, pp.NTilde)


def test_paillier_proof(preparams):
    sk = preparams["node0"].paillier
    proof = zk.PaillierProof.prove(sk)
    assert proof.verify(sk.public)
    other = preparams["node1"].paillier.public
    assert not proof.verify(other)


def test_schnorr_and_pedersen():
    x = secrets.randbelow(zk.Q - 1) + 1
    X = hm.secp_mul(x, hm.SECP_G)
    p = zk.SchnorrProof.prove(x, X)
    assert p.verify(X)
    assert not p.verify(hm.secp_mul(x + 1, hm.SECP_G))

    a, b = (secrets.randbelow(zk.Q) for _ in range(2))
    R = hm.secp_mul(7, hm.SECP_G)
    V = hm.secp_add(hm.secp_mul(a, R), hm.secp_mul(b, hm.SECP_G))
    pp = zk.PedersenPoK.prove(a, b, R, V)
    assert pp.verify(R, V)
    assert not pp.verify(R, hm.secp_add(V, hm.SECP_G))


def test_mta_roundtrip(preparams):
    alice, bob = preparams["node0"], preparams["node1"]
    pk_a = alice.paillier.public
    a = secrets.randbelow(zk.Q)
    b = secrets.randbelow(zk.Q)
    init, _ = mta.mta_init(pk_a, bob.NTilde, bob.h1, bob.h2, a)
    resp, beta = mta.mta_respond(
        pk_a,
        alice.NTilde, alice.h1, alice.h2,
        bob.NTilde, bob.h1, bob.h2,
        init, b, with_check=False,
    )
    alpha = mta.mta_finalize(
        alice.paillier, alice.NTilde, alice.h1, alice.h2, init, resp
    )
    assert (alpha + beta) % zk.Q == a * b % zk.Q


def test_mta_with_check_binds_point(preparams):
    alice, bob = preparams["node0"], preparams["node1"]
    pk_a = alice.paillier.public
    a, b = secrets.randbelow(zk.Q), secrets.randbelow(zk.Q)
    init, _ = mta.mta_init(pk_a, bob.NTilde, bob.h1, bob.h2, a)
    resp, beta = mta.mta_respond(
        pk_a,
        alice.NTilde, alice.h1, alice.h2,
        bob.NTilde, bob.h1, bob.h2,
        init, b, with_check=True,
    )
    X = hm.secp_mul(b, hm.SECP_G)
    alpha = mta.mta_finalize(
        alice.paillier, alice.NTilde, alice.h1, alice.h2, init, resp, X=X
    )
    assert (alpha + beta) % zk.Q == a * b % zk.Q
    # wrong public point must be rejected
    with pytest.raises(ValueError):
        mta.mta_finalize(
            alice.paillier, alice.NTilde, alice.h1, alice.h2, init, resp,
            X=hm.secp_mul(b + 1, hm.SECP_G),
        )


def test_range_proof_rejects_negative_s1(preparams):
    """Regression: a negative s1 flips pow() into modular inverses and the
    equations verify for out-of-range plaintexts unless explicitly bounded."""
    import dataclasses

    alice, bob = preparams["node0"], preparams["node1"]
    pk_a = alice.paillier.public
    init, _ = mta.mta_init(pk_a, bob.NTilde, bob.h1, bob.h2, 42)
    assert init.proof.verify(pk_a, bob.NTilde, bob.h1, bob.h2, init.c_a)
    forged = dataclasses.replace(init.proof, s1=-init.proof.s1)
    assert not forged.verify(pk_a, bob.NTilde, bob.h1, bob.h2, init.c_a)


def test_bob_proof_rejects_oversized_beta_prime(preparams):
    """Regression: t1 ≤ q⁷ bound — β′ ≈ N would let Alice's decrypt-wrap
    behavior leak comparison bits on k_i."""
    alice, bob = preparams["node0"], preparams["node1"]
    pk_a = alice.paillier.public
    init, _ = mta.mta_init(pk_a, bob.NTilde, bob.h1, bob.h2, 42)
    b = secrets.randbelow(zk.Q)
    beta_prime = pk_a.N - zk.Q**6  # malicious: way beyond q⁵
    r = zk._rand_unit(pk_a.N)
    c_beta = pk_a.encrypt(beta_prime, r=r)
    c_b = pow(init.c_a, b, pk_a.N2) * c_beta % pk_a.N2
    proof = zk.RespProofBob.prove(
        pk_a, alice.NTilde, alice.h1, alice.h2, init.c_a, c_b, b, beta_prime, r
    )
    assert not proof.verify(pk_a, alice.NTilde, alice.h1, alice.h2, init.c_a, c_b)


def test_keygen_proofs_are_session_bound(preparams):
    """Regression: DLN/Paillier proofs replayed into a different wallet's
    keygen (different session id) must not verify."""
    pp = preparams["node0"]
    pq = (pp.P - 1) // 2 * ((pp.Q - 1) // 2)
    proof = zk.DLNProof.prove(pp.h1, pp.h2, pp.alpha, pq, pp.NTilde, bind=b"w1:node0")
    assert proof.verify(pp.h1, pp.h2, pp.NTilde, bind=b"w1:node0")
    assert not proof.verify(pp.h1, pp.h2, pp.NTilde, bind=b"w2:node1")
    pproof = zk.PaillierProof.prove(pp.paillier, bind=b"w1:node0")
    assert pproof.verify(pp.paillier.public, bind=b"w1:node0")
    assert not pproof.verify(pp.paillier.public, bind=b"w2:node1")


def test_keygen_produces_consistent_wallet(wallets):
    pubs = {w.public_key for w in wallets.values()}
    assert len(pubs) == 1  # same public key everywhere
    # shares interpolate to the secret behind the pubkey (test-only!)
    xs = [w.self_x for w in wallets.values()]
    secret = 0
    for w in wallets.values():
        lam = hm.lagrange_coeff(xs, w.self_x, zk.Q)
        secret = (secret + lam * w.share) % zk.Q
    assert hm.secp_compress(hm.secp_mul(secret, hm.SECP_G)) == next(iter(pubs))
    w0 = next(iter(wallets.values()))
    assert len(w0.vss_commitments) == 2  # t+1 aggregated commitments
    assert len(w0.aux["peer_paillier"]) == 2


@pytest.mark.parametrize("quorum", [["node0", "node1"], ["node0", "node2"]])
def test_signing_end_to_end(wallets, quorum):
    digest = int.from_bytes(secrets.token_bytes(32), "big")
    parties = {
        pid: ECDSASigningParty(
            f"tx-{quorum[-1]}", pid, quorum, wallets[pid], digest
        )
        for pid in quorum
    }
    run_protocol(parties)
    results = [p.result for p in parties.values()]
    assert all(r == results[0] for r in results)
    r, s, rec = results[0]["r"], results[0]["s"], results[0]["recovery"]
    assert s <= zk.Q // 2  # low-s
    pub = hm.secp_decompress(next(iter(wallets.values())).public_key)
    assert hm.ecdsa_verify(pub, digest, r, s)
    # independent verification via OpenSSL
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec, utils

    pn = ec.EllipticCurvePublicNumbers(pub.x, pub.y, ec.SECP256K1())
    key = pn.public_key()
    sig = utils.encode_dss_signature(r, s)
    key.verify(
        sig, digest.to_bytes(32, "big"), ec.ECDSA(utils.Prehashed(hashes.SHA256()))
    )
