"""Batched signing engine vs host-math ground truth."""
import hashlib
import secrets

import jax.numpy as jnp
import numpy as np
import pytest

from mpcium_tpu.core import bignum as bn
from mpcium_tpu.core import ed25519_jax as ed
from mpcium_tpu.core import hostmath as hm
from mpcium_tpu.core.bignum import P256 as PROF
from mpcium_tpu.engine import eddsa_batch as eb


def test_bytes_limbs_roundtrip():
    rng = np.random.default_rng(0)
    b = rng.integers(0, 256, size=(5, 32), dtype=np.uint8)
    limbs = bn.bytes_to_limbs_le(jnp.asarray(b), PROF, PROF.n_limbs)
    vals = bn.batch_from_limbs(np.asarray(limbs), PROF)
    expect = [int.from_bytes(row.tobytes(), "little") for row in b]
    assert vals == expect
    back = np.asarray(bn.limbs_to_bytes_le(limbs, PROF, 32))
    assert (back == b).all()


def test_limbs_to_bits():
    vals = [0, 1, hm.ED_L - 1, 2**252 + 12345]
    limbs = jnp.asarray(bn.batch_to_limbs(vals, PROF))
    bits = np.asarray(bn.limbs_to_bits(limbs, PROF, 256))
    for i, v in enumerate(vals):
        got = sum(int(bit) << j for j, bit in enumerate(bits[i]))
        assert got == v


@pytest.mark.slow  # 22 s alone on XLA:CPU: the decompress compile at this width
def test_decompress_valid_points():
    pts = [hm.ed_mul(k, hm.ED_B) for k in (1, 2, 3, 12345, hm.ED_L - 1)]
    enc = np.stack(
        [np.frombuffer(hm.ed_compress(p), dtype=np.uint8) for p in pts]
    )
    dec, ok = ed.decompress(jnp.asarray(enc))
    assert np.asarray(ok).all()
    for i, p in enumerate(pts):
        got = ed.to_host(
            ed.EdPointJ(dec.X[i], dec.Y[i], dec.Z[i], dec.T[i])
        )[0]
        assert got.equals(p)


def test_decompress_rejects_garbage():
    bad = np.full((2, 32), 0xFF, dtype=np.uint8)  # y = 2^255-1 ≥ p
    _, ok = ed.decompress(jnp.asarray(bad))
    assert not np.asarray(ok).any()


def test_nonce_commitments_match_host():
    r64 = eb.fresh_nonce_bytes(4, secrets)
    r_limbs, R_comp = eb.nonce_commitments(jnp.asarray(r64))
    for i in range(4):
        r_int = int.from_bytes(r64[i].tobytes(), "little") % hm.ED_L
        assert bn.from_limbs(np.asarray(r_limbs)[i], PROF) == r_int
        expect = hm.ed_compress(hm.ed_mul(r_int, hm.ED_B))
        assert np.asarray(R_comp)[i].tobytes() == expect


@pytest.mark.slow  # 48 s a quorum alone: the whole engine compiles
@pytest.mark.parametrize("q,t", [(3, 2), (2, 1)])
def test_batched_cosigning_end_to_end(q, t):
    B = 8
    # universe of 3 parties, quorum = first q of them (sorted)
    universe = ["node0", "node1", "node2"]
    shares = eb.dealer_keygen_batch(B, universe, t, rng=secrets)
    quorum_ids = sorted(universe)[:q]
    quorum_shares = shares[:q]
    signer = eb.BatchedCoSigners(quorum_ids, quorum_shares, rng=secrets)
    messages = [f"tx-{i}".encode() for i in range(B)]
    sigs, ok = signer.sign(messages)
    assert ok.all()
    # independent host-side RFC 8032 verification
    for i in range(B):
        pub = quorum_shares[0][i].public_key
        assert hm.ed25519_verify(pub, messages[i], sigs[i].tobytes())


@pytest.mark.slow  # 55 s: the verify kernel compiles
def test_batched_verify_rejects_wrong_message():
    B = 4
    universe = ["a", "b", "c"]
    shares = eb.dealer_keygen_batch(B, universe, 1, rng=secrets)
    signer = eb.BatchedCoSigners(sorted(universe)[:2], shares[:2], rng=secrets)
    messages = [f"m{i}".encode() for i in range(B)]
    sigs, ok = signer.sign(messages)
    assert ok.all()
    A = jnp.asarray(signer.A_comp)
    wrong = eb.challenge_hashes(
        np.asarray(sigs[:, :32]), signer.A_comp, [b"evil"] * B
    )
    bad = eb.verify_signatures(jnp.asarray(sigs), A, jnp.asarray(wrong))
    assert not np.asarray(bad).any()
