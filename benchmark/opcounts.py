"""Limb multiply-adds the engine's five signing kernels need, from shapes
only. No JAX, no program import: the counts follow the published algorithms
the engine implements (``mpcium_tpu/core/{bignum,fields,ed25519_jax}.py``,
``engine/eddsa_batch.py``), re-derived here so a later PR cannot move them.

Unit: one multiply-add of two 12-bit limbs into a 32-bit column sum.
Additions, carries, selects and byte packing are not counted (they are
linear in the limbs; the multiplications are quadratic and dominate), and
neither is SHA-512 of the challenge (no limb arithmetic).

Field GF(2^255-19), 22 limbs of 12 bits (bignum.P256):
  field multiplication = schoolbook 22 x 22                       = 484
    + pseudo-Mersenne fold: the 22 high limbs x the 2-limb fold
      constant (19 << 9 needs 14 bits = 2 limbs)                  =  44
    + second fold pass: 1 high limb x 2                            =   2
                                                            total = 530
  (the engine's one-hot einsum spends 22 x 22 x 43 per product; that is
  its way of doing it, not what the algorithm needs)

Scalar ring mod l, Barrett (bignum.BarrettCtx.reduce), n = 22:
  reduce = q1 (23 limbs) x mu (24 limbs) + q3 (23) x m (23) = 552 + 529 = 1081
  mulmod = 22 x 22 + reduce                                         = 1565

Group (ed25519_jax):
  add (unified, add-2008-hwcd-3): A, B, T1*T2, *2d, Z1*Z2, and the four
    output products                                    = 9 field mults
  pow_const(e): one squaring per bit of e, one multiplication per set bit
    (left-to-right square and multiply)                = bits(e) + ones(e)
  inv = pow_const(p - 2)
  compress = inv + 2
  decompress = y^2, d*y^2, v^3 (2), v^7 (2), u*v^7, pow((p-5)/8),
    u*v^3*pw (2), v*x^2 (2), x*sqrt(-1), x*y         = 13 + pow((p-5)/8)
  base_mul: 256 table additions (fixed-base, no doublings) = 256 adds
  scalar_mul: 256 x (add + double)                         = 512 adds
  equal: 4
"""
from __future__ import annotations

N_LIMBS = 22
FOLD_LIMBS = 2
P = 2**255 - 19
SCALAR_BITS = 256

FIELD_MUL = N_LIMBS * N_LIMBS + N_LIMBS * FOLD_LIMBS + 1 * FOLD_LIMBS
BARRETT_REDUCE = (N_LIMBS + 1) * (N_LIMBS + 2) + (N_LIMBS + 1) * (N_LIMBS + 1)
SCALAR_MULMOD = N_LIMBS * N_LIMBS + BARRETT_REDUCE

POINT_ADD = 9  # field multiplications


def pow_const_mults(exponent: int) -> int:
    return exponent.bit_length() + bin(exponent).count("1")


INV = pow_const_mults(P - 2)
COMPRESS = INV + 2
DECOMPRESS = 13 + pow_const_mults((P - 5) // 8)
BASE_MUL = SCALAR_BITS * POINT_ADD
SCALAR_MUL = 2 * SCALAR_BITS * POINT_ADD
EQUAL = 4


def nonce_commitments(lanes: int) -> int:
    """r64 -> r mod l (one Barrett reduction), R = r*B, compress(R)."""
    return lanes * (BARRETT_REDUCE + (BASE_MUL + COMPRESS) * FIELD_MUL)


def aggregate_nonce(q: int, lanes: int) -> int:
    """q decompressions, q-1 additions, one compression, per lane."""
    return lanes * (q * DECOMPRESS + (q - 1) * POINT_ADD + COMPRESS) * FIELD_MUL


def partial_signature(lanes: int) -> int:
    """c64 -> c mod l, s_i = r + c * lambda_i x_i (one mulmod)."""
    return lanes * (BARRETT_REDUCE + SCALAR_MULMOD)


def combine_signatures(q: int, lanes: int) -> int:
    """q-1 modular additions and byte packing: no multiplications."""
    return 0


def verify_signatures(lanes: int) -> int:
    """decompress R and A, c mod l, s*B, c*A, one addition, equality."""
    return lanes * (BARRETT_REDUCE
                    + (2 * DECOMPRESS + BASE_MUL + SCALAR_MUL + POINT_ADD
                       + EQUAL) * FIELD_MUL)


KERNELS = ("nonce_commitments", "aggregate_nonce", "partial_signature",
           "combine_signatures", "verify_signatures")


def per_wave(wave: int, q: int) -> dict:
    """Limb multiply-adds of one wave of ``wave`` signatures with a served
    quorum of ``q``: every one of the q parties runs all five kernels over
    all lanes (cohorts split the lanes, not the work)."""
    one_party = {
        "nonce_commitments": nonce_commitments(wave),
        "aggregate_nonce": aggregate_nonce(q, wave),
        "partial_signature": partial_signature(wave),
        "combine_signatures": combine_signatures(q, wave),
        "verify_signatures": verify_signatures(wave),
    }
    return {k: q * v for k, v in one_party.items()}
