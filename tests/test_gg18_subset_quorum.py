"""GG18 below n-of-n, in tier-1: the curve-side round programs run as TWO
signers of a wave of 2, for each of the three 2-subsets of a 2-of-3
universe, against the plain reference beside this file
(``gg18_subset_reference.py``: threshold ECDSA over a signer subset in
Python integers) and OpenSSL.

The wave and the rounds are ``tests/test_gg18_round_programs.py``'s own
(``_Wave(ids=...)``, ``_sign``: three signers there, every node READY), so
here too the MtA's outcome is dealt on the host (additive shares of k·γ
and of k·x among the SIGNERS), because XLA:CPU cannot compile the MtA
programs inside tier-1; the whole party at q = 2 is the slow tier's,
through the degraded cell's own rehearsal. What is new here is the shape:
``gg18_setup``, ``gg18_r5a_verify``, ``gg18_r5c_verify``, ``gg18_r5e`` and
``gg18_final`` take q in their operands' shapes (the peers' blocks stack
``(q − 1, B, n)`` in ``others()`` order), and the Lagrange weights are
those of a subset whose members need not include rank 0 ({node1, node2} is
the one the cell ``secp-2of3-paillier-degraded.gg18-node-down-waves``
serves at). Held to the reference on the way (W_j, Σδ, R, r, s_i, the low
s and the recovery id) and to OpenSSL at the end, and each in-protocol
check still fails its lane alone at q = 2.
"""
import itertools

import numpy as np
import pytest
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec, utils

import gg18_subset_reference as ref
from mpcium_tpu.core import hostmath as hm
from mpcium_tpu.core import secp256k1_jax as sp
from test_gg18_round_programs import (B, IDS, XS, Q, _comp, _flip, _setup,
                                      _sign, _Wave)

SUBSETS = [list(s) for s in itertools.combinations(IDS, 2)]
CELLS_SIGNERS = ["node1", "node2"]  # node0 out: the degraded cell's quorum


def _reference(w: _Wave):
    """Each lane of the wave as the reference sees it: (the signers'
    additive shares, what it signs)."""
    m = [int.from_bytes(d, "big") % Q for d in w.digests]
    shares = [ref.additive_shares({p: w.share[p][i] for p in w.ids}, XS)
              for i in range(B)]
    signed = [ref.sign(m[i], {p: w.k[p][i] for p in w.ids},
                       {p: w.g[p][i] for p in w.ids}, shares[i])
              for i in range(B)]
    return m, shares, signed


# -- the reference's own identities ---------------------------------------------

@pytest.mark.parametrize("ids", SUBSETS + [IDS], ids="+".join)
def test_the_signers_additive_shares_add_up_to_the_dealt_key(ids):
    """Σ_{i∈S} λ_i·x_i = x for every 2-subset (and the whole committee),
    the reference's weights are ``hostmath``'s (which the party uses), and
    the public forms are the additive shares' multiples of G."""
    w = _Wave(seed=3, ids=ids)
    _m, shares, _signed = _reference(w)
    at = [XS[p] for p in ids]
    for p in ids:
        assert ref.lagrange_at_zero(at, XS[p]) == hm.lagrange_coeff(
            at, XS[p], Q)
    for i in range(B):
        assert shares[i] == {p: w.w[p][i] for p in ids}
        assert sum(shares[i].values()) % Q == w.keys[i]
        W = ref.public_shares([row[i] for row in w.commit], ids, XS)
        total = hm.SECP_INF
        for p in ids:
            assert W[p] == hm.secp_mul(shares[i][p], hm.SECP_G)
            total = hm.secp_add(total, W[p])
        assert total == w.pub[i]


def test_the_reference_signs_what_plain_ecdsa_signs():
    """k = Σk_i and x = Σw_i: the reference's (r, s) is plain ECDSA's under
    the nonce k⁻¹ (GG18's R = k⁻¹·G), accepted by ``hostmath`` and low; the
    dealt shares of k·γ and k·x are shares of its δ and σ."""
    w = _Wave(seed=5, ids=CELLS_SIGNERS)
    m, _shares, signed = _reference(w)
    for i, got in enumerate(signed):
        k = w.ksum[i]
        assert got["delta"] == sum(w.delta[p][i] for p in w.ids) % Q
        assert got["sigma"] == sum(w.sigma[p][i] for p in w.ids) % Q
        assert got["sigma"] == k * w.keys[i] % Q
        assert got["R"] == hm.secp_mul(pow(k, -1, Q), hm.SECP_G)
        # s = k·(m + r·x): ECDSA's s for the nonce k⁻¹
        assert got["s"] == k * (m[i] + got["r"] * w.keys[i]) % Q
        assert got["s_low"] in (got["s"], Q - got["s"])
        assert 0 < got["s_low"] <= Q // 2
        assert hm.ecdsa_verify(w.pub[i], m[i], got["r"], got["s_low"])
        assert sum(ref.partial(m[i], got["r"], w.k[p][i], w.sigma[p][i])
                   for p in w.ids) % Q == got["s"]


# -- two signers of a wave of 2, each 2-subset ----------------------------------

@pytest.mark.parametrize("ids", SUBSETS, ids="+".join)
def test_two_signers_of_any_two_nodes_sign_what_openssl_accepts(ids):
    w = _Wave(seed=45, ids=ids)
    m, _shares, signed = _reference(w)
    _Y, W_pts, W_comps, ok, _m = _setup(w)
    assert bool(np.asarray(ok).all()) and len(W_pts) == len(ids) == 2
    for i in range(B):
        public = ref.public_shares([row[i] for row in w.commit], ids, XS)
        for j, p in enumerate(ids):
            assert sp.to_host(W_pts[j])[i] == public[p], (p, i)
            assert bytes(np.asarray(W_comps[j])[i]) == _comp(public[p])
    out, seen = _sign(w)
    assert seen["delta"] == [s["delta"] for s in signed]
    assert seen["R"] == [s["R"] for s in signed]
    assert seen["r"] == [s["r"] for s in signed]
    for p in ids:
        assert seen["s_i"][p] == [
            ref.partial(m[i], signed[i]["r"], w.k[p][i], w.sigma[p][i])
            for i in range(B)]
    first = out[ids[0]]
    for p in ids:
        r, s, rec, ok = out[p]
        assert ok.all(), p
        assert all((a == b).all() for a, b in zip(out[p], first))
        for i in range(B):
            ri = int.from_bytes(bytes(r[i]), "big")
            si = int.from_bytes(bytes(s[i]), "big")
            assert (ri, si, int(rec[i])) == (
                signed[i]["r"], signed[i]["s_low"], signed[i]["recovery"])
            ec.EllipticCurvePublicKey.from_encoded_point(
                ec.SECP256K1(), _comp(w.pub[i])
            ).verify(utils.encode_dss_signature(ri, si), w.digests[i],
                     ec.ECDSA(utils.Prehashed(hashes.SHA256())))


# -- the in-protocol checks at q = 2 --------------------------------------------

@pytest.mark.parametrize("rnd,field,lane,verdict", [
    (4, "spok", 1, "ok5a"),    # a tampered Schnorr response
    (4, "blind", 0, "ok5a"),   # Γ's decommitment does not open its commitment
    (6, "sa", 0, "ok5c"),      # a tampered Pedersen response
    (6, "c", 1, "ok5c"),       # (V, A) revealed against another commitment
])
def test_a_bad_block_fails_its_lane_alone_at_q2(rnd, field, lane, verdict):
    w = _Wave(seed=11, ids=CELLS_SIGNERS)

    def tamper(at, blocks):
        if at == rnd:
            _flip(blocks, "node1", field, lane)

    out, seen = _sign(w, tamper)
    want = np.ones((B,), bool)
    want[lane] = False
    # the signer whose block was altered checks only its one peer's: sound
    assert seen[verdict]["node1"].all()
    assert (seen[verdict]["node2"] == want).all()
    assert (out["node2"][3] == want).all()


def test_a_reveal_that_does_not_open_or_shares_that_do_not_add_up_at_q2():
    # phase 5E: a (U, T) decommitment altered in one lane
    w = _Wave(seed=13, ids=CELLS_SIGNERS)

    def tamper(at, blocks):
        if at == 8:
            _flip(blocks, "node2", "blind", 1)

    out, _seen = _sign(w, tamper)
    assert list(out["node1"][3]) == [True, False]
    assert list(out["node2"][3]) == [True, True]
    # one signer's share of k·x off by one in lane 0: ΣU != ΣT there, at
    # both signers, and the lane's signature is withheld
    out, _seen = _sign(_Wave(seed=13, sigma_off=[0], ids=CELLS_SIGNERS))
    for p in CELLS_SIGNERS:
        assert list(out[p][3]) == [False, True], p
