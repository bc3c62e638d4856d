"""Batched secp256k1 group operations in JAX.

Projective points (X:Y:Z) on y² = x³ + 7 with the *complete* addition
formulas of Renes–Costello–Batina 2015 (Algorithm 7, short Weierstrass
a = 0): one branch-free formula valid for every input pair, including
doubling and the identity (0:1:0). Completeness costs ~40% more field muls
than dedicated Jacobian add/double but removes all data-dependent control
flow — the right trade for XLA/TPU batching (SURVEY.md §7).

Hot-path design: a curve program's time on the chip is its count of field
operations run one after another (each ends in sequential carry scans over
the limbs), not their width. So (1) the independent field operations of
the addition run as ONE operation over a leading stack axis, and every
linear step of the formula reaches the reduction as a raw limb sum: an
addition is four field operations in a row (two stacked products, two
stacked sums; `tests/test_secp_chain_length.py` pins the count); (2) both
ladders take 4-bit windows, and a window's entry is read by a one-hot sum
over the sixteen entries: selects only, so no address depends on a digit
of a secret scalar (SECURITY.md); (3) the field's inverse and square root
are addition chains (`fields.Secp256k1Field`).

This is the curve under GG18 ECDSA (reference uses tss.S256() via
btcec/dcrec — pkg/mpc/ecdsa_keygen_session.go:83); the hot ops are the nonce
commitments Γ_i = γ_i·G and R reconstruction in the signing rounds.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from . import bignum as bn
from . import hostmath as hm
from .fields import secp256k1_field

PROF = bn.P256
SCALAR_BITS = 256
_B3 = 21  # 3·b for b = 7


class SecpPointJ(NamedTuple):
    """Batch of projective points; fields shaped (..., 22)."""

    X: jnp.ndarray
    Y: jnp.ndarray
    Z: jnp.ndarray

    @property
    def batch_shape(self):
        return self.X.shape[:-1]


def identity(batch_shape=()) -> SecpPointJ:
    F = secp256k1_field()
    return SecpPointJ(
        F.const(0, batch_shape), F.const(1, batch_shape), F.const(0, batch_shape)
    )


def from_host(points) -> SecpPointJ:
    """hostmath.SecpPoint list (no identities) → batch."""
    F = secp256k1_field()
    xs = [p.x for p in points]
    ys = [p.y for p in points]
    return SecpPointJ(
        jnp.asarray(F.from_ints(xs)),
        jnp.asarray(F.from_ints(ys)),
        F.const(1, (len(points),)),
    )


def to_host(p: SecpPointJ) -> list:
    """Batch → list of affine hostmath.SecpPoint (identity-aware)."""
    F = secp256k1_field()
    zs = F.to_ints(p.Z)
    xs = F.to_ints(p.X)
    ys = F.to_ints(p.Y)
    out = []
    for x, y, z in zip(xs, ys, zs):
        if z == 0:
            out.append(hm.SECP_INF)
        else:
            zi = pow(z, -1, hm.SECP_P)
            out.append(hm.SecpPoint(x * zi % hm.SECP_P, y * zi % hm.SECP_P))
    return out


@functools.lru_cache(maxsize=None)
def _kp_limbs() -> np.ndarray:
    """K·p ≥ 2^264 (the field's borrow-free subtraction offset) as 22
    limbs, the top one above the radix: k of it added to a limb sum that
    subtracts k field elements keeps the total non-negative."""
    kp = secp256k1_field().kp_limbs.copy()
    kp[-2] += kp[-1] << PROF.bits
    return kp[:-1]


def _sums(*rows: jnp.ndarray) -> jnp.ndarray:
    """Integer-linear combinations of field elements, each row given as
    its raw limb sum (non-negative total below 2^276, limbs of either
    sign far inside int32), reduced as ONE field operation over a leading
    stack axis: one carry, one fold."""
    F = secp256k1_field()
    return F.fold(bn.carry(bn.pad_limbs(jnp.stack(rows), 1), PROF))


def _muls_of_sums(xs, ys) -> jnp.ndarray:
    """The products xs[i]·ys[i] as ONE field multiplication over a leading
    stack axis, each operand a field element or the RAW limb sum of two
    (limbs ≤ 8,190): a column is at most 22·8,190² < 2^31, the product
    below 2^530, so it is carried into 45 limbs, one more than `bn.mul`
    pads for normalized operands."""
    n = PROF.n_limbs
    cols = jnp.einsum("...i,...j,ijn->...n", jnp.stack(xs), jnp.stack(ys),
                      jnp.asarray(bn._conv_tensor(n, n)))
    return secp256k1_field().fold(bn.carry(bn.pad_limbs(cols, 2), PROF))


def add(a: SecpPointJ, b: SecpPointJ) -> SecpPointJ:
    """Complete addition, RCB15 Algorithm 7 (a=0, b3=21).

    Four field operations one after another: the formula's twelve
    multiplications are two stacked products of six, and each of its
    additions, subtractions and small multiples is a raw limb sum handed
    to the reduction that follows it. The opening sums (X+Y, Y+Z, X+Z of
    each operand) go into the first product unreduced; everything between
    the products is one stacked :func:`_sums`, and so are the three
    closing sums. Operands are normalized (limbs < 2^12) and so are the
    results: the bounds in `_sums` and `_muls_of_sums` rest on that."""
    F = secp256k1_field()
    shape = jnp.broadcast_shapes(a.X.shape, b.X.shape)
    aX, aY, aZ, bX, bY, bZ = (
        jnp.broadcast_to(c, shape) for c in (*a, *b)
    )
    t0, t1, t2, m3, m4, m5 = _muls_of_sums(
        [aX, aY, aZ, aX + aY, aY + aZ, aX + aZ],
        [bX, bY, bZ, bX + bY, bY + bZ, bX + bZ],
    )
    kp = _kp_limbs()
    t2b = _B3 * t2
    # 3·t0, t1 + b3·t2, t1 − b3·t2, the cross terms t3 and t4, b3·(cross)
    k0, z3, t1, t3, t4, y3 = _sums(
        3 * t0,
        t1 + t2b,
        t1 - t2b + _B3 * kp,
        m3 - t0 - t1 + 2 * kp,
        m4 - t1 - t2 + 2 * kp,
        _B3 * (m5 - t0 - t2 + 2 * kp),
    )
    w = F.mul(jnp.stack([t4, t3, y3, t1, k0, z3]),
              jnp.stack([y3, t1, k0, z3, t3, t4]))
    return SecpPointJ(*_sums(w[1] - w[0] + kp, w[3] + w[2], w[5] + w[4]))


def double(a: SecpPointJ) -> SecpPointJ:
    return add(a, a)


def select(mask: jnp.ndarray, a: SecpPointJ, b: SecpPointJ) -> SecpPointJ:
    m = mask[..., None]
    return SecpPointJ(
        jnp.where(m, a.X, b.X), jnp.where(m, a.Y, b.Y), jnp.where(m, a.Z, b.Z)
    )


def scalars_to_bits(ks, n_bits: int = SCALAR_BITS) -> np.ndarray:
    out = np.zeros((len(ks), n_bits), dtype=np.int32)
    for i, k in enumerate(ks):
        assert 0 <= k < 1 << n_bits
        for j in range(n_bits):
            out[i, j] = (k >> j) & 1
    return out


# Both ladders take 4-bit windows. At the batch widths a served wave has
# (tens of lanes) a ladder's time is its count of point additions one
# after another, not their width: a window step is four doublings and one
# addition of a table entry chosen per lane, so a 256-bit scalar costs 334
# additions (14 to build the lane's table) where double-and-add cost 512;
# the fixed base's table is a constant, so k·G costs 64.
_WINDOW = 4
_N_WINDOWS = SCALAR_BITS // _WINDOW


def _digits(bits: jnp.ndarray) -> jnp.ndarray:
    """(..., 256) bits LSB-first → (64, ...) window digits, the most
    significant window first."""
    w = bits.reshape(bits.shape[:-1] + (_N_WINDOWS, _WINDOW))
    d = jnp.sum(w << jnp.arange(_WINDOW, dtype=jnp.int32), axis=-1)
    return jnp.moveaxis(d, -1, 0)[::-1].astype(jnp.int32)


def _pick(table: jnp.ndarray, d: jnp.ndarray) -> SecpPointJ:
    """table (3, 16, ..., 22), one entry a digit; d (...,) → each lane's
    entry as a one-hot sum: fifteen selects, no address that depends on
    the digit."""
    ks = jnp.arange(1 << _WINDOW, dtype=jnp.int32).reshape((-1,) + (1,) * d.ndim)
    hot = (d[None] == ks)[None, ..., None]
    return SecpPointJ(*jnp.sum(jnp.where(hot, table, 0), axis=1))


def scalar_mul(bits: jnp.ndarray, p: SecpPointJ) -> SecpPointJ:
    """Variable-base k·P by 4-bit windows; bits (..., 256) LSB-first (a
    shorter scalar is zero-extended)."""
    n_bits = bits.shape[-1]
    if n_bits < SCALAR_BITS:
        bits = jnp.pad(
            bits, [(0, 0)] * (bits.ndim - 1) + [(0, SCALAR_BITS - n_bits)]
        )
    batch = bits.shape[:-1]

    def next_row(row, _):
        row = add(row, p)
        return row, row

    _, more = lax.scan(next_row, p, None, length=(1 << _WINDOW) - 2)
    table = jnp.stack([
        jnp.concatenate([jnp.stack([c0, c1]), cs])
        for c0, c1, cs in zip(identity(batch), p, more)
    ])  # (3, 16, ..., 22)

    def step(acc, d):
        entry = _pick(table, d)

        # four doublings and the entry's addition as five runs of ONE
        # compiled addition (the last with the entry for its second term)
        def run(i, a):
            other = SecpPointJ(*(
                jnp.where(i < _WINDOW, c, e) for c, e in zip(a, entry)
            ))
            return add(a, other)

        return lax.fori_loop(0, _WINDOW + 1, run, acc), None

    acc, _ = lax.scan(step, identity(batch), _digits(bits))
    return acc


@functools.lru_cache(maxsize=None)
def _base_table() -> np.ndarray:
    """Constants d·16^i·G for window i in [0, 64), digit d in [0, 16): a
    (64, 3, 16, 22) int32 array of X, Y, Z, the entry of digit 0 the
    identity (0:1:0)."""
    F = secp256k1_field()
    rows = []
    base = hm.SECP_G
    for _ in range(_N_WINDOWS):
        rows.append((0, 1, 0))
        cur = base
        for _d in range(1, 1 << _WINDOW):
            rows.append((cur.x, cur.y, 1))
            cur = hm.secp_add(cur, base)
        base = cur  # 16·base
    flat = np.asarray(F.from_ints([v for row in rows for v in row]))
    return np.ascontiguousarray(flat.reshape(
        _N_WINDOWS, 1 << _WINDOW, 3, PROF.n_limbs
    ).transpose(0, 2, 1, 3))


def base_mul(bits: jnp.ndarray) -> SecpPointJ:
    """Fixed-base k·G: one addition a 4-bit window from the table of
    d·16^i·G (no doublings)."""
    digits = _digits(bits)[::-1]  # the table's window 0 is the lowest
    table = jnp.asarray(_base_table())
    # every lane reads the same entries: lane axes of extent 1
    table = table.reshape(table.shape[:3] + (1,) * (bits.ndim - 1) + table.shape[3:])

    def step(acc, sl):
        d, rows = sl
        return add(acc, _pick(rows, d)), None

    acc, _ = lax.scan(step, identity(bits.shape[:-1]), (digits, table))
    return acc


@functools.lru_cache(maxsize=None)
def scalar_ring() -> bn.BarrettCtx:
    """Barrett context for the group order n (the ECDSA scalar ring)."""
    return bn.BarrettCtx(hm.SECP_N, PROF)


def neg(a: SecpPointJ) -> SecpPointJ:
    """Batch point negation (Y ↦ -Y)."""
    F = secp256k1_field()
    return SecpPointJ(a.X, F.neg(a.Y), a.Z)


def equal(a: SecpPointJ, b: SecpPointJ) -> jnp.ndarray:
    """Batch equality: cross-multiplied, Z-invariant, identity-aware."""
    F = secp256k1_field()
    ex = F.eq(F.mul(a.X, b.Z), F.mul(b.X, a.Z))
    ey = F.eq(F.mul(a.Y, b.Z), F.mul(b.Y, a.Z))
    za = F.is_zero(a.Z)
    zb = F.is_zero(b.Z)
    return jnp.where(za | zb, za == zb, ex & ey)


def x_coordinate(p: SecpPointJ) -> jnp.ndarray:
    """Affine x as canonical limbs (the ECDSA r source)."""
    F = secp256k1_field()
    return F.canonical(F.mul(p.X, F.inv(p.Z)))


def compress(p: SecpPointJ) -> jnp.ndarray:
    """Batch SEC1 compressed encoding → (..., 33) uint8 big-endian."""
    F = secp256k1_field()
    zi = F.inv(p.Z)
    x = F.canonical(F.mul(p.X, zi))
    y = F.canonical(F.mul(p.Y, zi))
    xb = pack_be_32(x)
    tag = (2 + (y[..., 0] & 1)).astype(jnp.uint8)
    return jnp.concatenate([tag[..., None], xb], axis=-1)


def pack_be_32(limbs: jnp.ndarray) -> jnp.ndarray:
    """Canonical limbs (< 2^256) → (..., 32) uint8 big-endian."""
    shifts = jnp.arange(PROF.bits, dtype=jnp.int32)
    bits = (limbs[..., :, None] >> shifts) & 1  # LSB-first
    bits = bits.reshape(limbs.shape[:-1] + (PROF.n_limbs * PROF.bits,))[..., :256]
    by = bits.reshape(bits.shape[:-1] + (32, 8))
    vals = jnp.sum(by << jnp.arange(8, dtype=jnp.int32), axis=-1)
    return jnp.flip(vals, axis=-1).astype(jnp.uint8)


@functools.lru_cache(maxsize=None)
def _sqrt_ctx():
    from .fields import Secp256k1Sqrt

    return Secp256k1Sqrt()


def decompress(b: jnp.ndarray) -> "Tuple[SecpPointJ, jnp.ndarray]":
    """Batch SEC1 decompression: (..., 33) uint8 → (SecpPointJ, ok mask).

    Bad encodings (wrong tag, x ≥ p, non-residue) yield ok=False with an
    arbitrary valid-shape point — callers gate on the mask (the device
    analogue of hostmath.secp_decompress raising)."""
    F = secp256k1_field()
    tag = b[..., 0].astype(jnp.int32)
    xb = jnp.flip(b[..., 1:], axis=-1)  # big-endian bytes → little-endian
    x = bn.bytes_to_limbs_le(xb, PROF, PROF.n_limbs)
    p_l = jnp.broadcast_to(jnp.asarray(bn.to_limbs(hm.SECP_P, PROF)), x.shape)
    ok = (bn.compare(x, p_l) < 0) & ((tag == 2) | (tag == 3))
    rhs = F.add(F.mul(F.square(x), x), F.const(7, x.shape[:-1]))
    y, has_root = _sqrt_ctx().sqrt(rhs)
    ok = ok & has_root
    y = F.canonical(y)
    flip = (y[..., 0] & 1) != (tag & 1)
    y = jnp.where(flip[..., None], F.canonical(F.neg(y)), y)
    one = jnp.broadcast_to(
        jnp.asarray(bn.to_limbs(1, PROF)), x.shape
    )
    return SecpPointJ(x, y, one), ok
