"""The secp256k1 scheme file the cell ``secp-2of3-paillier.gg18-waves`` is
measured by (benchmark/schemes/secp256k1.py), as far as it needs no GG18
compile: its wallets, its plain reference, its rows of the check, its
operation counts against counts worked out by hand, and that what it names
(round programs, phase spans) is what the program has."""
import os
import random
from types import SimpleNamespace

import pytest
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec, utils

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "secp-2of3-paillier.gg18-waves"
IDS = ["node0", "node1", "node2"]


@pytest.fixture(scope="module")
def scheme():
    return harness.Cell(ROOT, CELL).scheme


@pytest.fixture(scope="module")
def preparams():
    from mpcium_tpu.cluster import load_test_preparams

    return load_test_preparams(bits=1024)


@pytest.fixture(scope="module")
def wallets(scheme, preparams):
    return scheme.make_wallets(4, IDS, 1, random.Random(2147492801),
                               preparams)


def _secret(scheme, records, w, pair):
    """The key two nodes' shares of wallet ``w`` interpolate to."""
    (xa, sa), (xb, sb) = [(records[n][w].self_x, records[n][w].share)
                          for n in pair]
    inv = pow(xb - xa, -1, scheme.N)
    return (sa * xb - sb * xa) * inv % scheme.N


@pytest.mark.parametrize("pair", [("node0", "node1"), ("node0", "node2"),
                                  ("node1", "node2")])
def test_any_two_shares_interpolate_to_the_openssl_key(scheme, wallets, pair):
    pubkeys, records = wallets
    for w, pub in enumerate(pubkeys):
        assert scheme._point(_secret(scheme, records, w, pair)) == pub
        assert all(records[n][w].public_key == pub for n in IDS)


def test_the_feldman_commitments_open_every_share(wallets):
    from mpcium_tpu.core import hostmath as hm

    _pubkeys, records = wallets
    for nid in IDS:
        for rec in records[nid]:
            c0, c1 = (hm.secp_decompress(c) for c in rec.vss_commitments)
            want = hm.secp_add(c0, hm.secp_mul(rec.self_x, c1))
            assert hm.secp_mul(rec.share, hm.SECP_G) == want
            assert rec.vss_commitments[0] == rec.public_key


def test_the_aux_is_what_the_programs_dealer_writes(wallets, preparams):
    from mpcium_tpu.engine.gg18_batch import dealer_keygen_secp_batch

    _pubkeys, records = wallets
    dealt = dealer_keygen_secp_batch(1, IDS, 1, preparams=preparams)
    for i, nid in enumerate(IDS):
        for rec in records[nid]:
            assert rec.aux == dealt[i][0].aux
            assert set(rec.aux["peer_paillier"]) == set(IDS) - {nid}
            assert set(rec.aux["peer_ring_pedersen"]) == set(IDS) - {nid}
            assert rec.participants == dealt[i][0].participants
            assert (rec.threshold, rec.key_type) == (1, "secp256k1")


def test_two_seeds_give_other_wallets_and_one_seed_the_same(scheme, preparams):
    one = scheme.make_wallets(2, IDS, 1, random.Random(7), preparams)[0]
    assert one == scheme.make_wallets(2, IDS, 1, random.Random(7),
                                      preparams)[0]
    assert one != scheme.make_wallets(2, IDS, 1, random.Random(8),
                                      preparams)[0]


def _openssl_signature(scheme, secret, digest):
    der = ec.derive_private_key(secret, ec.SECP256K1()).sign(
        digest, ec.ECDSA(utils.Prehashed(hashes.SHA256())))
    r, s = utils.decode_dss_signature(der)
    return r.to_bytes(32, "big") + s.to_bytes(32, "big")


@pytest.fixture(scope="module")
def signed(scheme, wallets):
    pubkeys, records = wallets
    digest = bytes(range(32))
    secret = _secret(scheme, records, 0, ("node0", "node1"))
    return pubkeys, digest, _openssl_signature(scheme, secret, digest)


def test_the_reference_accepts_openssls_own_signature(scheme, signed):
    pubkeys, digest, sig = signed
    assert scheme.verifies(pubkeys[0], digest, sig)


@pytest.mark.parametrize("what", ["r", "s", "key", "digest", "length"])
def test_the_reference_rejects(scheme, signed, what):
    pubkeys, digest, sig = signed
    pub = pubkeys[0]
    if what == "r":
        sig = bytes([sig[0] ^ 1]) + sig[1:]
    elif what == "s":
        sig = sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]
    elif what == "key":
        pub = pubkeys[1]
    elif what == "digest":
        digest = digest[::-1]
    else:
        sig = sig[:-1]
    assert not scheme.verifies(pub, digest, sig)


def test_verifies_needs_nothing_of_the_program(scheme):
    import inspect

    assert "mpcium_tpu" not in inspect.getsource(scheme.verifies)


def _req(sig, success=True):
    return SimpleNamespace(success=success, signature=sig)


# the group order (SEC 2, section 2.4.1)
ORDER = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141


@pytest.mark.parametrize("s,waves,rows", [
    (ORDER - 1, 4, (1, 0)),          # high
    (ORDER // 2 + 1, 1, (1, 1)),     # the first high value; one wave counted
    (ORDER // 2, 2, (0, 0)),         # the last low value; two waves
    (1, 0, (0, 2)),
])
def test_the_schemes_rows_count_high_s_and_missing_waves(
        scheme, s, waves, rows):
    sig = (5).to_bytes(32, "big") + s.to_bytes(32, "big")
    failed_high = _req((5).to_bytes(32, "big") + scheme.N.to_bytes(32, "big"),
                       success=False)  # a failed request has no signature
    run = SimpleNamespace(measured=[_req(sig), failed_high],
                          measured_waves=[object()] * waves,
                          config=harness.Cell(ROOT, CELL).config)
    got = scheme.check_rows(None, run)
    assert got == {"high_s_signatures": (rows[0], "==", 0),
                   "counted_waves_short_of_two": (rows[1], "==", 0)}


def test_the_result_event_reads_as_r_then_s(scheme):
    ev = SimpleNamespace(r="aa" * 32, s="bb" * 32, signature="")
    assert scheme.result_signature(ev) == "aa" * 32 + "bb" * 32


# -- operation counts ---------------------------------------------------------
# worked out by hand from the header of secp256k1_opcounts.py:
#   field multiplication 576; scalar mulmod 1565; inversion = 256 bits +
#   249 ones of p - 2 = 505 multiplications, compress 507
#   k*G then compress, and two scalar mulmods (a lane of round 4, and the
#   curve part of round 1): (3072 + 507) * 576 + 2 * 1565   = 2,064,634
#   a mulmod mod N^2 (586 limbs): 586^2 + 2 * 587^2          = 1,032,534
#     of which on the MXU: 2 * 587^2                          =   689,138
#   Enc: 32 comb windows, m x N (293^2 = 85,849), one mulmod
#     = 32 * 1,032,534 + 85,849 + 1,032,534                  = 34,159,471
#     MXU: 32 * 689,138 + 85,849 + 689,138                   = 22,827,403
LANE_R4 = 2_064_634
LANE_R1 = LANE_R4 + 34_159_471
BY_HAND = {
    (1, 2): {"gg18_r3_delta": 2 * 2 * 1565, "gg18_r4_pok": 2 * LANE_R4,
             "gg18_r1_commit": 2 * LANE_R1},
    (32, 3): {"gg18_r3_delta": 3 * 32 * 2 * 1565,
              "gg18_r4_pok": 3 * 32 * LANE_R4,
              "gg18_r1_commit": 3 * 32 * LANE_R1},
}
# every program's count summed, pinned (a change to any formula shows here)
TOTALS = {(1, 2): (23_641_306_500, 15_686_799_362),
          (32, 3): (2_263_322_090_790, 1_504_718_623_302)}


@pytest.mark.parametrize("shape", sorted(BY_HAND))
def test_the_operation_counts_are_the_counts_by_hand(scheme, shape):
    ops = scheme.ops_per_wave(*shape)
    mxu = scheme.mxu_ops_per_wave(*shape)
    assert set(ops) == set(mxu) == set(scheme.KERNELS)
    for name, want in BY_HAND[shape].items():
        assert ops[name] == want, name
    q, wave = shape[1], shape[0]
    assert mxu["gg18_r1_commit"] == q * wave * 22_827_403
    assert (round(sum(ops.values())), round(sum(mxu.values()))) == (
        TOTALS[shape])
    # curve work has no MXU part; no program's MXU part passes its whole
    assert all(mxu[k] <= ops[k] for k in ops)
    assert mxu["gg18_r5c_verify"] == mxu["gg18_final"] == 0


def test_the_counts_grow_with_the_wave_and_the_quorum(scheme):
    small = sum(scheme.ops_per_wave(16, 3).values())
    assert sum(scheme.ops_per_wave(32, 3).values()) > 1.99 * small
    # q signers, q - 1 peers each: the MtA work grows with q (q - 1)
    two, three = (scheme.ops_per_wave(32, q)["gg18_r2_respond"]
                  for q in (2, 3))
    assert three == pytest.approx(two * (3 * 2) / (2 * 1))


def test_the_programs_and_phases_named_are_the_programs_own(scheme):
    """``gg18.achieved_gops`` and its neighbours find the round programs
    as ``jit_<function name>``; ``gg18.phase_ms_per_wave`` the spans by
    name: the scheme file names exactly what the program has."""
    from mpcium_tpu.engine import gg18_batch as gb
    from mpcium_tpu.protocol.ecdsa import batch_signing as bs

    named = {n for names in gb.ROUND_PROGRAMS.values() for n in names}
    assert named == set(scheme.KERNELS)
    for name in scheme.KERNELS:
        fn = getattr(gb, name)
        assert hasattr(fn, "lower") and fn.__name__ == name
    assert set(scheme.MTA_KERNELS) == set(
        gb.ROUND_PROGRAMS[2] + gb.ROUND_PROGRAMS[3])
    assert scheme.PHASE_SPANS == bs.PHASE_SPANS
    assert scheme.ENGINE == "party.ecdsa"
    with open(bs.__file__) as fh:
        source = fh.read()
    import re

    called = set(re.findall(r"\bgb\.(gg18_\w+)\(", source))
    assert called == set(scheme.KERNELS)


def test_the_configuration_states_the_programs_own_security_parameters():
    """2048-bit Paillier keys, the default proof domains, the default
    randomizer width: nothing shrunk outside the rehearsal's overlay."""
    from mpcium_tpu.engine.gg18_batch import Domains
    from mpcium_tpu.ops.paillier_mxu import RAND_BITS

    cell = harness.Cell(ROOT, CELL)
    sizes = cell.config["scheme"]
    assert sizes["paillier_bits"] == 2048 and not sizes["proof_domains"]
    assert cell.scheme._domains(cell.config) == Domains()
    ops = cell.scheme.opcounts
    assert (ops.ALPHA, ops.BETA_PRIME, ops.GAMMA_BOB, ops.RHO_EXTRA) == (
        Domains().alpha, Domains().beta_prime, Domains().gamma_bob,
        Domains().rho_extra)
    assert ops.RAND_BITS == RAND_BITS and ops.PAILLIER_BITS == 2048
    assert cell.config["serving"]["batch_max_batch"] in (16, 32, 64)
    assert cell.traffic["wave_timeout_s"] == 60.0
    assert cell.config["population"]["wallets"] >= 1024
