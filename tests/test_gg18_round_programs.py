"""The curve-side GG18 round programs against host arithmetic, on the CPU.

The whole batched party cannot be compiled by XLA:CPU inside tier-1 (its
MtA programs take tens of minutes there), so the slow GG18 suites are the
end-to-end checks. This file keeps, in tier-1, what needs no Paillier
modulus: the programs of the set-up, of rounds 3 to 9 and of the final
combination run as three signers of a wave of 2 would run them, with the
MtA's outcome (additive shares of k·γ and k·x) dealt on the host. Held to
``core.hostmath`` on the way (W_j, Σδ, R, r, s_i) and to OpenSSL at the end
(every signer's r ‖ s verifies under the wallet's key and s is low), and
each in-protocol check is shown to fail its lane alone: a tampered Schnorr
response, a decommitment that does not open its commitment, a tampered
Pedersen response, shares of k·x that do not add up.
"""
import hashlib
import random

import numpy as np
import pytest
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec, utils

from mpcium_tpu.core import bignum as bn
from mpcium_tpu.core import hostmath as hm
from mpcium_tpu.core import secp256k1_jax as sp
from mpcium_tpu.engine import gg18_batch as gb

Q = hm.SECP_N
WIRE_DIGEST_AT_PR_43 = (
    "11965ea807853982999e026785cc928e91888a9700046c339782189c71c29d92")
IDS = ["node0", "node1", "node2"]
XS = {"node0": 1, "node1": 2, "node2": 3}
B = 2


def _limbs(vals):
    return bn.batch_to_limbs([v % Q for v in vals], bn.P256)


def _ints(limbs):
    return bn.batch_from_limbs(np.asarray(limbs), bn.P256)


def _be32(vals):
    return np.stack([
        np.frombuffer((v % Q).to_bytes(32, "big"), np.uint8) for v in vals])


def _bytes(rng, *shape):
    return np.frombuffer(rng.randbytes(int(np.prod(shape))),
                         np.uint8).reshape(shape)


def _bind(pid):
    h = hashlib.sha256(f"bsign:t:{pid}".encode()).digest()
    return np.tile(np.frombuffer(h, np.uint8), (B, 1))


def _comp(pt: hm.SecpPoint) -> bytes:
    return hm.secp_compress(pt)


class _Wave:
    """One wave of B wallets dealt to the three nodes, as the signers
    ``ids`` (all three; ``tests/test_gg18_subset_quorum.py``: any two) hold
    it after round 3, the MtA's outcome dealt on the host among the
    signers; ``sigma_off`` adds to one signer's share of k·x in the given
    lanes (shares that do not add up)."""

    def __init__(self, seed=7, sigma_off=None, ids=IDS):
        rng = self.rng = random.Random(seed)
        self.ids = ids
        self.keys = [rng.randrange(1, Q) for _ in range(B)]
        coef = [rng.randrange(1, Q) for _ in range(B)]  # threshold 1
        self.share = {p: [(x + c * XS[p]) % Q for x, c in zip(self.keys, coef)]
                      for p in IDS}
        at = [XS[p] for p in ids]
        lam = {p: hm.lagrange_coeff(at, XS[p], Q) for p in ids}
        self.lam = lam
        self.w = {p: [lam[p] * s % Q for s in self.share[p]] for p in ids}
        self.pub = [hm.secp_mul(x, hm.SECP_G) for x in self.keys]
        self.commit = [[hm.secp_mul(x, hm.SECP_G) for x in self.keys],
                       [hm.secp_mul(c, hm.SECP_G) for c in coef]]
        self.digests = [rng.randbytes(32) for _ in range(B)]
        self.k = {p: [rng.randrange(1, Q) for _ in range(B)] for p in ids}
        self.g = {p: [rng.randrange(1, Q) for _ in range(B)] for p in ids}
        ksum = [sum(self.k[p][i] for p in ids) % Q for i in range(B)]
        gsum = [sum(self.g[p][i] for p in ids) % Q for i in range(B)]
        self.ksum = ksum

        def split(total):
            parts = [rng.randrange(Q) for _ in ids[1:]]
            return parts + [(total - sum(parts)) % Q]

        d = [split(ksum[i] * gsum[i] % Q) for i in range(B)]
        s = [split(ksum[i] * self.keys[i] % Q) for i in range(B)]
        self.delta = {p: [d[i][j] for i in range(B)]
                      for j, p in enumerate(ids)}
        self.sigma = {p: [s[i][j] for i in range(B)]
                      for j, p in enumerate(ids)}
        for lane in sigma_off or ():
            self.sigma["node1"][lane] = (self.sigma["node1"][lane] + 1) % Q


def _setup(w: _Wave):
    return gb.gg18_setup(
        np.stack([np.frombuffer(_comp(p), np.uint8) for p in w.pub]),
        np.stack([np.stack([np.frombuffer(_comp(c), np.uint8) for c in row])
                  for row in w.commit]),
        np.stack([np.frombuffer(d, np.uint8) for d in w.digests]),
        sp.scalars_to_bits([XS[p] for p in w.ids], n_bits=8),
        sp.scalars_to_bits([w.lam[p] for p in w.ids]),
    )


def _stack(blocks, pid, field):
    """The peers' blocks as the party stacks them, (q − 1, B, n): every
    signer's but ``pid``'s own, in the signers' order."""
    return np.stack([np.asarray(blocks[j][field]) for j in blocks
                     if j != pid])


def _sign(w: _Wave, tamper=None):
    """Rounds 4 to 9 and the combination for every signer → {signer: (r
    block, s block, recovery ids, ok lanes)}, and the values met on the
    way. ``tamper(round, blocks)`` may alter what the peers receive."""
    tamper = tamper or (lambda rnd, blocks: None)
    rng = w.rng
    Y, _W_pts, _W_comps, ok0, m = _setup(w)
    assert bool(np.asarray(ok0).all())
    seen = {}
    # round 1's curve half and round 4: Γ_i, its commitment, the PoK of γ_i
    r4 = {}
    gam = {}
    for p in w.ids:
        blind = _bytes(rng, B, 32)
        pt, comp, commit = gb._blk_gamma(_limbs(w.g[p]), blind, _bind(p))
        A, spok = gb.gg18_r4_pok(_bytes(rng, B, 40), _limbs(w.g[p]), comp,
                                 _bind(p))
        gam[p] = pt
        r4[p] = {"G": np.asarray(comp), "blind": blind,
                 "gc": np.asarray(commit), "A": np.asarray(A),
                 "spok": np.asarray(spok), "d": _be32(w.delta[p]),
                 "bind": _bind(p)}
    tamper(4, r4)
    st, r5 = {}, {}
    for p in w.ids:
        peers = {f: _stack(r4, p, f) for f in r4[p]}
        ok, delta, Gsum = gb.gg18_r5a_verify(
            np.ones((B,), bool), _limbs(w.delta[p]), gam[p], peers)
        blind = _bytes(rng, B, 32)
        s = gb.gg18_r5a_commit(
            ok, delta, Gsum, m, _limbs(w.k[p]), _limbs(w.sigma[p]),
            {x: _bytes(rng, B, 40) for x in ("li", "rho", "ka", "kb")},
            blind, _bind(p))
        st[p] = dict(s, va_blind=blind)
        seen.setdefault("delta", _ints(delta))
        seen.setdefault("R", sp.to_host(s["R"]))
        seen.setdefault("r", _ints(s["r"]))
        seen.setdefault("ok5a", {})[p] = np.asarray(s["ok"])
        seen.setdefault("s_i", {})[p] = _ints(s["s"])
        r5[p] = {"c": np.asarray(s["commit"])}
    r6 = {}
    for p in w.ids:
        s = st[p]
        Apok, sa, sb = gb.gg18_r5b(s["ka"], s["kb"], s["s"], s["li"], s["R"],
                                   s["vc"], s["ac"], _bind(p))
        r6[p] = {"vc": np.asarray(s["vc"]), "ac": np.asarray(s["ac"]),
                 "blind": s["va_blind"], "c": r5[p]["c"],
                 "apok": np.asarray(Apok), "sa": np.asarray(sa),
                 "sb": np.asarray(sb), "bind": _bind(p)}
    tamper(6, r6)
    r8 = {}
    for p in w.ids:
        s = st[p]
        peers = {f: _stack(r6, p, f) for f in r6[p]}
        ok, Vsum, Asum = gb.gg18_r5c_verify(s["ok"], s["V"], s["A"], s["R"],
                                            peers)
        seen.setdefault("ok5c", {})[p] = np.asarray(ok)
        blind = _bytes(rng, B, 32)
        u = gb.gg18_r5c_commit(Vsum, Asum, m, s["r"], Y, s["rho"], s["li"],
                               blind, _bind(p))
        s.update(ok=ok, U=u["U"], T=u["T"])
        r8[p] = {"uc": np.asarray(u["uc"]), "tc": np.asarray(u["tc"]),
                 "blind": blind, "c": np.asarray(u["commit"]),
                 "bind": _bind(p)}
    tamper(8, r8)
    r9 = {}
    for p in w.ids:
        s = st[p]
        peers = {f: _stack(r8, p, f) for f in r8[p]}
        s["ok"], block = gb.gg18_r5e(s["ok"], s["U"], s["T"], s["s"], peers)
        r9[p] = {"s": np.asarray(block)}
    out = {}
    for p in w.ids:
        s = st[p]
        r, sig, rec, ok = gb.gg18_final(
            s["ok"], s["s"], _stack(r9, p, "s"), m, s["r"], s["rec"], Y)
        out[p] = tuple(np.asarray(x) for x in (r, sig, rec, ok))
    wire = hashlib.sha256()
    for blocks in (r4, r5, r6, r8, r9):
        for p in w.ids:
            for field in sorted(blocks[p]):
                wire.update(np.ascontiguousarray(blocks[p][field]).tobytes())
    for p in w.ids:
        for x in out[p]:
            wire.update(x.tobytes())
    seen["wire"] = wire.hexdigest()
    return out, seen


@pytest.fixture(scope="module")
def honest():
    w = _Wave()
    return w, _sign(w)


def test_setup_gives_every_members_public_share_and_the_digests():
    w = _Wave()
    Y, W_pts, W_comps, ok, m = _setup(w)
    assert bool(np.asarray(ok).all())
    assert sp.to_host(Y) == w.pub
    for j, p in enumerate(IDS):
        want = [hm.secp_mul(x, hm.SECP_G) for x in w.w[p]]
        assert sp.to_host(W_pts[j]) == want
        assert [bytes(r) for r in np.asarray(W_comps[j])] == [
            _comp(pt) for pt in want]
    assert _ints(m) == [int.from_bytes(d, "big") % Q for d in w.digests]


def test_round_3_adds_the_legs_up():
    rng = random.Random(3)
    vals = lambda: [rng.randrange(Q) for _ in range(B)]  # noqa: E731
    k, g, w_ = vals(), vals(), vals()
    legs = [[vals() for _ in range(4)] for _ in range(2)]  # a_g a_w b_g b_w
    d, s, block = gb.gg18_r3_delta(
        _limbs(k), _limbs(g), _limbs(w_),
        tuple((_limbs(x[0]), _limbs(x[1])) for x in legs),
        tuple((_limbs(x[2]), _limbs(x[3])) for x in legs))
    want_d = [(k[i] * g[i] + sum(x[0][i] + x[2][i] for x in legs)) % Q
              for i in range(B)]
    want_s = [(k[i] * w_[i] + sum(x[1][i] + x[3][i] for x in legs)) % Q
              for i in range(B)]
    assert _ints(d) == want_d and _ints(s) == want_s
    assert [bytes(r) for r in np.asarray(block)] == [
        v.to_bytes(32, "big") for v in want_d]


def test_an_honest_wave_signs_what_openssl_accepts(honest):
    w, (out, seen) = honest
    kinv = [pow(k, -1, Q) for k in w.ksum]
    R = [hm.secp_mul(ki, hm.SECP_G) for ki in kinv]
    assert seen["R"] == R
    assert seen["r"] == [pt.x % Q for pt in R]
    assert seen["delta"] == [
        sum(w.delta[p][i] for p in IDS) % Q for i in range(B)]
    m = [int.from_bytes(d, "big") % Q for d in w.digests]
    for p in IDS:
        assert seen["s_i"][p] == [
            (m[i] * w.k[p][i] + seen["r"][i] * w.sigma[p][i]) % Q
            for i in range(B)]
    first = out[IDS[0]]
    for p in IDS:
        r, s, rec, ok = out[p]
        assert ok.all(), p
        assert all((a == b).all() for a, b in zip(out[p], first))
        for i in range(B):
            ri = int.from_bytes(bytes(r[i]), "big")
            si = int.from_bytes(bytes(s[i]), "big")
            assert ri == seen["r"][i] and 0 < si <= Q // 2
            ec.EllipticCurvePublicKey.from_encoded_point(
                ec.SECP256K1(), _comp(w.pub[i])
            ).verify(utils.encode_dss_signature(ri, si), w.digests[i],
                     ec.ECDSA(utils.Prehashed(hashes.SHA256())))
            assert int(rec[i]) & 1 == (R[i].y & 1) ^ (
                si != (kinv[i] * (m[i] + ri * w.keys[i])) % Q)


def test_the_wave_is_byte_for_byte_what_it_was_before_pr_44(honest):
    """Every block a signer sends in rounds 4 to 9 and every signature of
    the seeded wave, hashed in order: the digest PR 43's tree (commit
    f921b1d) gives for this file's `_Wave(seed=7)`. A change to the curve
    arithmetic under the round programs (PR 44: the four-call addition,
    the chains, the one-hot reads) moves no byte of the wire."""
    _w, (_out, seen) = honest
    assert seen["wire"] == WIRE_DIGEST_AT_PR_43


def _flip(blocks, pid, field, lane):
    a = np.array(blocks[pid][field])
    a[lane, -1] ^= 1
    blocks[pid][field] = a


@pytest.mark.parametrize("rnd,field,lane,verdict", [
    (4, "spok", 1, "ok5a"),    # a tampered Schnorr response
    (4, "blind", 0, "ok5a"),   # Γ's decommitment does not open its commitment
    (6, "sa", 0, "ok5c"),      # a tampered Pedersen response
    (6, "c", 1, "ok5c"),       # (V, A) revealed against another commitment
])
def test_a_bad_block_fails_its_lane_alone(rnd, field, lane, verdict):
    w = _Wave(seed=11)

    def tamper(at, blocks):
        if at == rnd:
            _flip(blocks, "node1", field, lane)

    out, seen = _sign(w, tamper)
    want = np.ones((B,), bool)
    want[lane] = False
    # the signer whose block was altered checks only the others': sound
    assert seen[verdict]["node1"].all()
    for p in ("node0", "node2"):
        assert (seen[verdict][p] == want).all(), p
        assert (out[p][3] == want).all(), p


def test_a_reveal_that_does_not_open_or_shares_that_do_not_add_up():
    # phase 5E: a (U, T) decommitment altered in one lane
    w = _Wave(seed=13)

    def tamper(at, blocks):
        if at == 8:
            _flip(blocks, "node2", "blind", 1)

    out, _seen = _sign(w, tamper)
    assert list(out["node0"][3]) == [True, False]
    assert list(out["node2"][3]) == [True, True]
    # one signer's share of k·x off by one in lane 0: ΣU != ΣT there, at
    # every signer, and the lane's signature is withheld
    out, _seen = _sign(_Wave(seed=13, sigma_off=[0]))
    for p in IDS:
        assert list(out[p][3]) == [False, True], p
