"""Limb multiply-adds the GG18 round programs need, from shapes only. No
JAX, no program import: the counts follow the algorithms the engine
implements (``mpcium_tpu/engine/gg18_batch.py`` ``gg18_*``,
``ops/modmul.py``, ``ops/paillier_mxu.py``, ``core/secp256k1_jax.py``),
re-derived here so a later PR cannot move them.

Unit: one multiply-add of two limbs into a column sum. Additions, carries,
selects, hashing and byte packing are not counted. Only what the algorithm
needs is counted: a product of an a-limb by a b-limb value is a x b, not
the padded or dense shape the engine may issue.

Two limb families.

Modular arithmetic mod N, N^2, NTilde, p^2, q^2, p, q: 7-bit limbs, a
modulus of ``bits`` bits occupies occ = ceil(bits / 7) limbs.
  product of two reduced values                          = occ^2
  Barrett reduction: q1 x mu and q3 x m, occ + 1 limbs each
                                                         = 2 (occ + 1)^2
  mulmod = product + reduction
  x^e, per-lane e of b bits, 4-bit windows: 4 squarings and 1 table
    multiplication a window, 14 to build the lane's table
                                                 = 5 ceil(b / 4) + 14 mulmods
  g^e, fixed g, comb of 8-bit windows: one a window   = ceil(b / 8) mulmods
  x times a constant of the modulus' width                = occ^2
On the chip (``ops/pallas_mulmod.py``, the default there) the product of
two lane values runs on the vector unit and the two Barrett constant
products on the MXU (bf16 operands, f32 sums), as do the products by a
constant (``modmul.mul_const``): ``mxu`` is that part of a count.

Curve secp256k1: 22 limbs of 12 bits (bignum.P256), all on the vector unit.
  field multiplication = schoolbook 22 x 22                       = 484
    + fold of the 22 high limbs by (2^32 + 977) << 8, 4 limbs     =  88
    + second fold pass, 1 high limb x 4                            =   4
                                                            total = 576
  point addition (complete, RCB15 algorithm 7)   = 12 field multiplications
    (doubling is the same addition)
  k*G by the table of G*2^i: 256 additions; k*P: 256 x (add + double)
  x^e for a constant e: bits(e) + ones(e) multiplications
  inversion = x^(p-2); compress = inversion + 2; affine x = inversion + 1
  decompress = x^2, x^3, the root x^((p+1)/4), its square   = 3 + pow
  equality = 4
  scalar ring mod n (Barrett, 22 limbs): reduce 1081, mulmod 1565 (as
  ``benchmark/opcounts.py`` counts Ed25519's)
"""
from __future__ import annotations

from typing import Dict, Tuple

# the configuration this counts for (benchmark/configs/secp-2of3-paillier
# .json): 2048-bit Paillier and ring-Pedersen moduli, the program's default
# proof domains (gg18_batch.Domains), threshold 1
PAILLIER_BITS = 2048
THRESHOLD = 1
SCALAR, ALPHA, BETA_PRIME, GAMMA_BOB, RHO_EXTRA = 256, 760, 1272, 1784, 248
RAND_BITS, RHO_BITS = 256, 128

P = 2**256 - 2**32 - 977
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141

# -- curve family -------------------------------------------------------------
FIELD_MUL = 22 * 22 + 22 * 4 + 4
BARRETT_REDUCE = 23 * 24 + 23 * 23
SCALAR_MULMOD = 22 * 22 + BARRETT_REDUCE
POINT_ADD = 12


def pow_const_mults(exponent: int) -> int:
    return exponent.bit_length() + bin(exponent).count("1")


INV = pow_const_mults(P - 2)
COMPRESS = INV + 2
AFFINE_X = INV + 1
DECOMPRESS = 3 + pow_const_mults((P + 1) // 4)
BASE_MUL = 256 * POINT_ADD
SCALAR_MUL = 2 * 256 * POINT_ADD
EQUAL = 4
SCALAR_INV = pow_const_mults(N - 2) * SCALAR_MULMOD


def curve(field_mults: int, scalar_mulmods: int = 0) -> Tuple[int, int]:
    """(multiply-adds, of which on the MXU) of curve work: none is."""
    return field_mults * FIELD_MUL + scalar_mulmods * SCALAR_MULMOD, 0


# -- modular family -----------------------------------------------------------
def occ(bits: int) -> int:
    return -(-bits // 7)


def limbs7(bits: int) -> int:
    """Whole 7-bit limbs of a proof-domain integer, in bits."""
    return occ(bits) * 7


def mulmod(bits: int, count: float = 1) -> Tuple[float, float]:
    o = occ(bits)
    return count * (o * o + 2 * (o + 1) ** 2), count * 2 * (o + 1) ** 2


def reduce_(bits: int) -> Tuple[float, float]:
    o = occ(bits)
    return 2 * (o + 1) ** 2, 2 * (o + 1) ** 2


def powmod(bits: int, ebits: int) -> Tuple[float, float]:
    return mulmod(bits, 5 * -(-ebits // 4) + 14)


def comb(bits: int, ebits: int) -> Tuple[float, float]:
    return mulmod(bits, -(-ebits // 8))


def const_mul(bits: int) -> Tuple[float, float]:
    o = occ(bits)
    return o * o, o * o


def int_mul(abits: int, bbits: int) -> Tuple[float, float]:
    """Plain product of two lane integers (vector unit)."""
    return occ(abits) * occ(bbits), 0


def total(*parts: Tuple[float, float]) -> Tuple[float, float]:
    return sum(p[0] for p in parts), sum(p[1] for p in parts)


def times(k: float, part: Tuple[float, float]) -> Tuple[float, float]:
    return k * part[0], k * part[1]


# proof-domain widths (gg18_batch.MtaBatch.__init__), in bits
NB, N2B, NTB = PAILLIER_BITS, 2 * PAILLIER_BITS, PAILLIER_BITS
S1 = limbs7(SCALAR + ALPHA + 7)
RHO = limbs7(SCALAR + NTB + RHO_EXTRA)
S2 = limbs7(SCALAR + RHO + 7)
T1 = limbs7(SCALAR + GAMMA_BOB + 7)
E_EXP = limbs7(2 * RAND_BITS + 8)  # the randomizer leg's exponent u e + u'


def commit(mbits: int, rbits: int) -> Tuple[float, float]:
    """h1^m h2^r mod NTilde: two combs and a mulmod."""
    return total(comb(NTB, mbits), comb(NTB, rbits), mulmod(NTB))


def encrypt() -> Tuple[float, float]:
    """(1 + m N) h^u mod N^2: the comb of h, m x N, one mulmod (the
    randomizer's value y^u is computed only where a proof needs it)."""
    return total(comb(N2B, RAND_BITS), const_mul(NB), mulmod(N2B))


def decrypt() -> Tuple[float, float]:
    """CRT: per prime r, c mod r^2, c^(r-1) mod r^2, L by r^-1, times h_r
    mod r; then (m_q - m_p) p^-1 mod q and m_p + p t."""
    half = total(reduce_(NB), powmod(NB, NB // 2), const_mul(NB),
                 mulmod(NB // 2))
    return total(times(2, half), times(2, reduce_(NB // 2)),
                 mulmod(NB // 2), const_mul(NB // 2))


# -- the programs, one call of each over ``lanes`` lanes ----------------------
def gg18_setup(lanes: int, q: int):
    t1 = THRESHOLD + 1
    per_member = (t1 - 1) * (2 * 8 + 1) * POINT_ADD + SCALAR_MUL + COMPRESS
    return times(lanes, curve((1 + t1) * DECOMPRESS + q * per_member, 1))


def gg18_r1_commit(lanes: int):
    return times(lanes, total(curve(BASE_MUL + COMPRESS, 2), encrypt()))


def gg18_r1_prove(lanes: int):
    return times(lanes, total(
        commit(SCALAR, RHO), encrypt(), commit(ALPHA, S2),
        comb(NB, E_EXP), int_mul(RAND_BITS, SCALAR),
        int_mul(SCALAR, SCALAR), int_mul(SCALAR, RHO)))


def _folds(lanes: int, products: int):
    """Products over the batch: lanes - 1 mulmods mod N^2 each."""
    return mulmod(N2B, products * (lanes - 1))


def gg18_r2_verify(lanes: int):
    per_lane = total(
        reduce_(NB), commit(S1, S2), powmod(NTB, SCALAR), mulmod(NTB),
        powmod(N2B, SCALAR), mulmod(N2B), times(2, powmod(N2B, RHO_BITS)),
        int_mul(RHO_BITS, NB))
    return total(times(lanes, per_lane), _folds(lanes, 2), const_mul(NB),
                 reduce_(NB))


def gg18_r2_respond(lanes: int, with_check: bool):
    per_lane = total(
        times(2, encrypt()), powmod(N2B, SCALAR), mulmod(N2B),
        powmod(N2B, ALPHA), mulmod(N2B),
        commit(SCALAR, RHO), commit(ALPHA, S2), commit(BETA_PRIME, RHO),
        commit(GAMMA_BOB, S2), comb(NB, E_EXP),
        int_mul(RAND_BITS, SCALAR), int_mul(SCALAR, SCALAR),
        int_mul(SCALAR, RHO), int_mul(SCALAR, BETA_PRIME),
        int_mul(SCALAR, RHO),
        curve(0, -(-BETA_PRIME // 176)))
    if with_check:
        per_lane = total(per_lane, curve(BASE_MUL + COMPRESS,
                                         -(-limbs7(ALPHA) // 176)))
    return times(lanes, per_lane)


def gg18_r3_verify(lanes: int, with_check: bool):
    per_lane = total(
        commit(S1, S2), powmod(NTB, SCALAR), mulmod(NTB),
        commit(T1, S2), powmod(NTB, SCALAR), mulmod(NTB),
        reduce_(NB), powmod(N2B, S1), const_mul(NB), mulmod(N2B),
        powmod(N2B, SCALAR), mulmod(N2B), times(3, powmod(N2B, RHO_BITS)),
        decrypt(), curve(0, -(-NB // 176)))
    if with_check:
        per_lane = total(per_lane, curve(
            DECOMPRESS + BASE_MUL + SCALAR_MUL + POINT_ADD + EQUAL,
            -(-S1 // 176) + -(-limbs7(SCALAR) // 176)))
    return total(times(lanes, per_lane), _folds(lanes, 3))


def gg18_r3_delta(lanes: int):
    return times(lanes, curve(0, 2))


def gg18_r4_pok(lanes: int):
    return times(lanes, curve(BASE_MUL + COMPRESS, 2))


def gg18_r5a_verify(lanes: int, q: int):
    peer = DECOMPRESS + BASE_MUL + SCALAR_MUL + POINT_ADD + COMPRESS
    return times(lanes, curve((q - 1) * (peer + POINT_ADD), 2 * (q - 1)))


def gg18_r5a_commit(lanes: int):
    own = (SCALAR_MUL + AFFINE_X + INV + 1
           + SCALAR_MUL + 2 * BASE_MUL + POINT_ADD + 2 * COMPRESS)
    return total(times(lanes, curve(own, 4 + 2)), (lanes * SCALAR_INV, 0))


def gg18_r5b(lanes: int):
    return times(lanes, curve(SCALAR_MUL + BASE_MUL + POINT_ADD + COMPRESS,
                              3))


def gg18_r5c_verify(lanes: int, q: int):
    peer = 2 * DECOMPRESS + 2 * SCALAR_MUL + BASE_MUL + 2 * POINT_ADD \
        + COMPRESS
    return times(lanes, curve((q - 1) * (peer + 2 * POINT_ADD),
                              3 * (q - 1)))


def gg18_r5c_commit(lanes: int):
    return times(lanes, curve(
        BASE_MUL + SCALAR_MUL + 2 * POINT_ADD + 2 * SCALAR_MUL
        + 2 * COMPRESS))


def gg18_r5e(lanes: int, q: int):
    return times(lanes, curve(
        (q - 1) * 2 * DECOMPRESS + 2 * (q - 1) * POINT_ADD + EQUAL))


def gg18_final(lanes: int, q: int):
    return total(
        times(lanes, curve(BASE_MUL + SCALAR_MUL + POINT_ADD + AFFINE_X, 3)),
        (lanes * SCALAR_INV, 0))


KERNELS = (
    "gg18_setup", "gg18_r1_commit", "gg18_r1_prove", "gg18_r2_verify",
    "gg18_r2_respond", "gg18_r3_verify", "gg18_r3_delta", "gg18_r4_pok",
    "gg18_r5a_verify", "gg18_r5a_commit", "gg18_r5b", "gg18_r5c_verify",
    "gg18_r5c_commit", "gg18_r5e", "gg18_final",
)

# the programs of wire rounds 2 and 3 (MtA respond; verify and decrypt)
MTA_KERNELS = ("gg18_r2_verify", "gg18_r2_respond", "gg18_r3_verify",
               "gg18_r3_delta")


def _per_wave(wave: int, q: int) -> Dict[str, Tuple[float, float]]:
    """One wave of ``wave`` signatures at a served quorum of ``q``: each of
    the q signers runs every program over all lanes; the per-peer programs
    run once a peer (q - 1), the response and its check once a peer and
    secret (gamma and w: the w leg adds the curve binding)."""
    pairs = q - 1
    one = {
        "gg18_setup": gg18_setup(wave, q),
        "gg18_r1_commit": gg18_r1_commit(wave),
        "gg18_r1_prove": times(pairs, gg18_r1_prove(wave)),
        "gg18_r2_verify": times(pairs, gg18_r2_verify(wave)),
        "gg18_r2_respond": times(pairs, total(
            gg18_r2_respond(wave, False), gg18_r2_respond(wave, True))),
        "gg18_r3_verify": times(pairs, total(
            gg18_r3_verify(wave, False), gg18_r3_verify(wave, True))),
        "gg18_r3_delta": gg18_r3_delta(wave),
        "gg18_r4_pok": gg18_r4_pok(wave),
        "gg18_r5a_verify": gg18_r5a_verify(wave, q),
        "gg18_r5a_commit": gg18_r5a_commit(wave),
        "gg18_r5b": gg18_r5b(wave),
        "gg18_r5c_verify": gg18_r5c_verify(wave, q),
        "gg18_r5c_commit": gg18_r5c_commit(wave),
        "gg18_r5e": gg18_r5e(wave, q),
        "gg18_final": gg18_final(wave, q),
    }
    return {k: times(q, v) for k, v in one.items()}


def per_wave(wave: int, q: int) -> Dict[str, float]:
    """Limb multiply-adds of one wave, by program."""
    return {k: v[0] for k, v in _per_wave(wave, q).items()}


def mxu_per_wave(wave: int, q: int) -> Dict[str, float]:
    """Of those, the multiply-adds the MXU carries (Barrett's two constant
    products of every mulmod, and the products by a constant)."""
    return {k: v[1] for k, v in _per_wave(wave, q).items()}
