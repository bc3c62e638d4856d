"""Batched edwards25519 group operations in JAX.

Points are extended twisted-Edwards coordinates (X:Y:Z:T) with x·y = T·Z,
each coordinate a 22-limb int32 tensor with arbitrary leading batch shape.
The unified addition law is *complete* on the curve (a = -1, d non-square):
no branches, valid for doubling, the identity and the points of small
order — exactly what XLA wants (SURVEY.md §7: compiler-friendly control
flow, static shapes). Doubling has a formula of its own (dbl-2008-hwcd),
also valid for every point.

Hot-path design: a ladder's time on the chip is its count of field
operations run one after another (each ends in sequential carry scans over
the limbs), not their width. So (1) the independent field operations of a
formula run as ONE operation over a leading stack axis: an addition is
four field operations in a row (two stacked products, two stacked sums), a
doubling three; (2) both ladders take 4-bit windows. Fixed-base k·B (nonce
commitments, keygen) is one addition a window from a constant table of
d·16^i·B, no doublings: 64 additions for 256 bits. Variable-base k·P
(verification) builds the lane's table 0·P … 15·P by 14 additions, then
takes four doublings and one table addition a window. Table entries are
kept in the form the addition consumes (Y−X, Y+X, 2d·T, 2Z; the constant
table's Z = 1 is implied). A window's entry is read by a one-hot sum over
the sixteen entries: selects only, so no address depends on a digit of a
secret scalar (SECURITY.md). Scalars of at most 16 bits (DKG's
x-coordinates) keep the bit-serial ladder: there the lane's table would
cost more than the windows save.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from . import bignum as bn
from . import hostmath as hm
from .fields import ed25519_field

PROF = bn.P256
SCALAR_BITS = 256


class EdPointJ(NamedTuple):
    """Batch of extended-coordinate points; fields shaped (..., 22)."""

    X: jnp.ndarray
    Y: jnp.ndarray
    Z: jnp.ndarray
    T: jnp.ndarray

    @property
    def batch_shape(self):
        return self.X.shape[:-1]


def identity(batch_shape=()) -> EdPointJ:
    F = ed25519_field()
    zero = F.const(0, batch_shape)
    one = F.const(1, batch_shape)
    return EdPointJ(zero, one, one, zero)


def from_host(points, batch_shape=None) -> EdPointJ:
    """Build a batch from host points (hostmath.EdPoint or (x, y) ints)."""
    F = ed25519_field()
    xs, ys = [], []
    for pt in points:
        x, y = pt.affine() if isinstance(pt, hm.EdPoint) else pt
        xs.append(x)
        ys.append(y)
    X = jnp.asarray(F.from_ints(xs))
    Y = jnp.asarray(F.from_ints(ys))
    T = F.mul(X, Y)
    Z = F.const(1, X.shape[:-1])
    return EdPointJ(X, Y, Z, T)


def to_host(p: EdPointJ) -> list:
    """Batch → list of hostmath.EdPoint (affine check included)."""
    F = ed25519_field()
    xs = F.to_ints(p.X)
    ys = F.to_ints(p.Y)
    zs = F.to_ints(p.Z)
    ts = F.to_ints(p.T)
    return [hm.EdPoint(x, y, z, t) for x, y, z, t in zip(xs, ys, zs, ts)]


@functools.lru_cache(maxsize=None)
def _kp_limbs() -> np.ndarray:
    """K·p ≥ 2^264 (the field's borrow-free subtraction offset) as 22
    limbs, the top one above the radix: added to a limb sum that
    subtracts one field element, it keeps the total non-negative."""
    kp = ed25519_field().kp_limbs.copy()
    kp[-2] += kp[-1] << PROF.bits
    return kp[:-1]


def _sums(*rows: jnp.ndarray) -> jnp.ndarray:
    """Sums and differences of field elements, each row given as its raw
    limb sum (non-negative total below 2^276), reduced as ONE field
    operation over a leading stack axis: one carry, one fold."""
    F = ed25519_field()
    return F.fold(bn.carry(bn.pad_limbs(jnp.stack(rows), 1), PROF))


def _muls(xs, ys) -> jnp.ndarray:
    """The products xs[i]·ys[i] as ONE field multiplication over a
    leading stack axis."""
    return ed25519_field().mul(jnp.stack(xs), jnp.stack(ys))


def _to_cached(p: EdPointJ) -> jnp.ndarray:
    """The form an addition wants of its second operand: the stack
    (Y−X, Y+X, 2Z, 2d·T), shaped (4, ..., 22)."""
    F = ed25519_field()
    s = _sums(p.Y - p.X + _kp_limbs(), p.Y + p.X, 2 * p.Z)
    t2d = F.mul(p.T, F.const(2 * hm.ED_D, p.T.shape[:-1]))
    return jnp.concatenate([s, t2d[None]])


def _add_cached(a: EdPointJ, c: jnp.ndarray) -> EdPointJ:
    """a + the point whose cached form (:func:`_to_cached`) is c. Unified
    and complete (HWCD08 'add-2008-hwcd-3'): any operand may be the
    identity, a + a is right too. c of three rows (Y−X, Y+X, 2d·T) is an
    affine point, Z = 1 implied: one product fewer."""
    kp = _kp_limbs()
    s = _sums(a.Y - a.X + kp, a.Y + a.X)
    if c.shape[0] == 3:
        A, B, C = _muls([s[0], s[1], a.T], list(c))
        D = 2 * a.Z
    else:
        A, B, D, C = _muls([s[0], s[1], a.Z, a.T], list(c))
    E, Fv, G, H = _sums(B - A + kp, D - C + kp, D + C, B + A)
    return EdPointJ(*_muls([E, G, Fv, E], [Fv, H, G, H]))


def add(a: EdPointJ, b: EdPointJ) -> EdPointJ:
    """Unified complete addition (RFC 8032 / HWCD08 'add-2008-hwcd-3')."""
    shape = jnp.broadcast_shapes(a.X.shape, b.X.shape)
    a, b = (EdPointJ(*(jnp.broadcast_to(c, shape) for c in p)) for p in (a, b))
    return _add_cached(a, _to_cached(b))


def double(a: EdPointJ) -> EdPointJ:
    """2a by HWCD08 'dbl-2008-hwcd' (a = -1): no 2d and no T read, valid
    for every point. With E = 2XY, G = Y²−X², H = Y²+X², F = 2Z²−G the
    result is (E·F : G·H : F·G : E·H), the formula's with all four
    coordinates negated: the same point."""
    xx, yy, zz, xy = _muls([a.X, a.Y, a.Z, a.X], [a.X, a.Y, a.Z, a.Y])
    kp = _kp_limbs()
    E, H, G, Fv = _sums(2 * xy, yy + xx, yy - xx + kp, 2 * zz - yy + xx + kp)
    return EdPointJ(*_muls([E, G, Fv, E], [Fv, H, G, H]))


def select(mask: jnp.ndarray, a: EdPointJ, b: EdPointJ) -> EdPointJ:
    """mask ? a : b, elementwise over the batch (mask: bool (...,))."""
    m = mask[..., None]
    return EdPointJ(
        jnp.where(m, a.X, b.X),
        jnp.where(m, a.Y, b.Y),
        jnp.where(m, a.Z, b.Z),
        jnp.where(m, a.T, b.T),
    )


def scalars_to_bits(ks, n_bits: int = SCALAR_BITS) -> np.ndarray:
    """Host ints → (batch, n_bits) int32 little-endian bit array."""
    out = np.zeros((len(ks), n_bits), dtype=np.int32)
    for i, k in enumerate(ks):
        assert 0 <= k < 1 << n_bits
        for j in range(n_bits):
            out[i, j] = (k >> j) & 1
    return out


_WINDOW = 4
# A lane's table costs 14 additions before the first window: up to this
# many bits the bit-serial ladder (an addition and a doubling a bit) is
# the shorter chain.
_BIT_SERIAL_MAX_BITS = 16


def _digits(bits: jnp.ndarray) -> jnp.ndarray:
    """(..., n) bits LSB-first → (ceil(n / 4), ...) window digits, the
    least significant window first."""
    pad = -bits.shape[-1] % _WINDOW
    if pad:
        bits = jnp.pad(bits, [(0, 0)] * (bits.ndim - 1) + [(0, pad)])
    w = bits.reshape(bits.shape[:-1] + (-1, _WINDOW))
    d = jnp.sum(w << jnp.arange(_WINDOW, dtype=jnp.int32), axis=-1)
    return jnp.moveaxis(d, -1, 0).astype(jnp.int32)


def _pick(table: jnp.ndarray, d: jnp.ndarray) -> jnp.ndarray:
    """table (rows, 16, ..., 22), one cached entry a digit; d (...,) →
    each lane's entry (rows, ..., 22) as a one-hot sum: fifteen selects,
    no address that depends on the digit."""
    ks = jnp.arange(1 << _WINDOW, dtype=jnp.int32).reshape((-1,) + (1,) * d.ndim)
    hot = (d[None] == ks)[None, ..., None]
    return jnp.sum(jnp.where(hot, table, 0), axis=1)


def _scalar_mul_bits(bits: jnp.ndarray, p: EdPointJ) -> EdPointJ:
    """Bit-serial double-and-add, for short scalars."""

    def step(carry, bit):
        acc, addend = carry
        acc = select(bit > 0, add(acc, addend), acc)
        return (acc, double(addend)), None

    init = (identity(bits.shape[:-1]), p)
    (acc, _), _ = lax.scan(step, init, jnp.moveaxis(bits, -1, 0))
    return acc


def scalar_mul(bits: jnp.ndarray, p: EdPointJ) -> EdPointJ:
    """Variable-base k·P; bits (..., n) LSB-first. By 4-bit windows over
    the lane's table of 0·P … 15·P, the count of windows following n; a
    scalar of at most 16 bits by double-and-add."""
    batch = bits.shape[:-1]
    p = EdPointJ(*(jnp.broadcast_to(c, batch + c.shape[-1:]) for c in p))
    if bits.shape[-1] <= _BIT_SERIAL_MAX_BITS:
        return _scalar_mul_bits(bits, p)
    cached_p = _to_cached(p)

    def next_row(row, _):
        row = _add_cached(row, cached_p)
        return row, row

    _, more = lax.scan(next_row, p, None, length=(1 << _WINDOW) - 2)
    table = _to_cached(EdPointJ(*(
        jnp.concatenate([jnp.stack([c0, c1]), cs])
        for c0, c1, cs in zip(identity(batch), p, more)
    )))  # (4, 16, ..., 22)

    def step(acc, d):
        # ONE compiled doubling, run four times; each keeps T, which rides
        # in the doubling's last stacked product at no operation's cost
        acc = lax.fori_loop(0, _WINDOW, lambda _, a: double(a), acc)
        return _add_cached(acc, _pick(table, d)), None

    acc, _ = lax.scan(step, identity(batch), _digits(bits)[::-1])
    return acc


@functools.lru_cache(maxsize=None)
def _base_table() -> np.ndarray:
    """Constants d·16^i·B for window i in [0, 64), digit d in [0, 16), in
    the cached form of an affine point (Y−X, Y+X, 2d·T; digit 0 the
    identity's 1, 1, 0): a (64, 3, 16, 22) int32 array."""
    F = ed25519_field()
    rows = []
    base = hm.ED_B
    for _ in range(SCALAR_BITS // _WINDOW):
        cur = hm.ED_IDENT
        for _d in range(1 << _WINDOW):
            x, y = cur.affine()
            rows.append((y - x, y + x, 2 * hm.ED_D * x * y))
            cur = hm.ed_add(cur, base)
        base = cur  # 16·base
    flat = np.asarray(F.from_ints([v for row in rows for v in row]))
    return np.ascontiguousarray(flat.reshape(
        SCALAR_BITS // _WINDOW, 1 << _WINDOW, 3, PROF.n_limbs
    ).transpose(0, 2, 1, 3))


def base_mul(bits: jnp.ndarray) -> EdPointJ:
    """Fixed-base k·B; bits (..., n ≤ 256) LSB-first. One addition a 4-bit
    window from the constant table of d·16^i·B, no doublings — the hot op
    for nonce commitments and keygen."""
    assert bits.shape[-1] <= SCALAR_BITS
    digits = _digits(bits)
    table = jnp.asarray(_base_table())[: digits.shape[0]]
    # every lane reads the same entries: lane axes of extent 1
    table = table.reshape(table.shape[:3] + (1,) * (bits.ndim - 1) + table.shape[3:])

    def step(acc, sl):
        d, rows = sl
        return _add_cached(acc, _pick(rows, d)), None

    acc, _ = lax.scan(step, identity(bits.shape[:-1]), (digits, table))
    return acc


@functools.lru_cache(maxsize=None)
def scalar_ring() -> bn.BarrettCtx:
    """Barrett context for the group order l (the EdDSA scalar ring)."""
    return bn.BarrettCtx(hm.ED_L, PROF)


def decompress(b: jnp.ndarray) -> "Tuple[EdPointJ, jnp.ndarray]":
    """Batch RFC 8032 decode: (..., 32) uint8 → (EdPointJ, ok mask).

    Invalid encodings (y ≥ p, non-residue x², x=0 with sign=1) yield the
    identity with ok=False — callers mask, never branch. Square root per
    p ≡ 5 (mod 8): x = u·v³·(u·v⁷)^((p-5)/8), fixed up by √-1.
    """
    F = ed25519_field()
    sign = (b[..., 31] >> 7).astype(jnp.int32)
    y_bytes = b.at[..., 31].set(b[..., 31] & 0x7F)
    y = bn.bytes_to_limbs_le(y_bytes, PROF, PROF.n_limbs)
    p_l = jnp.broadcast_to(jnp.asarray(bn.to_limbs(hm.ED_P, PROF)), y.shape)
    ok = bn.compare(y, p_l) < 0
    y2 = F.square(y)
    one = F.one_like(y2)
    u = F.sub(y2, one)
    v = F.add(F.mul(F.const(hm.ED_D, y.shape[:-1]), y2), one)
    v3 = F.mul(F.square(v), v)
    v7 = F.mul(F.square(v3), v)
    pw = F.pow_const(F.mul(u, v7), (hm.ED_P - 5) // 8)
    x = F.mul(F.mul(u, v3), pw)
    vx2 = F.mul(v, F.square(x))
    is_u = F.eq(vx2, u)
    is_neg_u = F.eq(vx2, F.neg(u))
    sqrt_m1 = F.const(pow(2, (hm.ED_P - 1) // 4, hm.ED_P), y.shape[:-1])
    x = jnp.where(is_neg_u[..., None], F.mul(x, sqrt_m1), x)
    ok = ok & (is_u | is_neg_u)
    xc = F.canonical(x)
    x_is_zero = jnp.all(xc == 0, axis=-1)
    ok = ok & ~(x_is_zero & (sign == 1))
    flip = (xc[..., 0] & 1) != sign
    x = jnp.where(flip[..., None], F.neg(x), x)
    pt = EdPointJ(x, y, F.one_like(y), F.mul(x, y))
    return select(ok, pt, identity(ok.shape)), ok


def equal(a: EdPointJ, b: EdPointJ) -> jnp.ndarray:
    """Batch equality, Z-invariant: X1·Z2 == X2·Z1 and Y1·Z2 == Y2·Z1."""
    F = ed25519_field()
    ex = F.eq(F.mul(a.X, b.Z), F.mul(b.X, a.Z))
    ey = F.eq(F.mul(a.Y, b.Z), F.mul(b.Y, a.Z))
    return ex & ey


def compress(p: EdPointJ) -> jnp.ndarray:
    """Batch compress → (..., 32) uint8, RFC 8032 encoding (little-endian y
    with sign bit of x in the top bit)."""
    F = ed25519_field()
    zi = F.inv(p.Z)
    x = F.canonical(F.mul(p.X, zi))
    y = F.canonical(F.mul(p.Y, zi))
    return _pack_bytes_le(y, sign=x[..., 0] & 1)


def _pack_bytes_le(limbs: jnp.ndarray, sign=None) -> jnp.ndarray:
    """Canonical 22×12-bit limbs → 32 bytes little-endian (values < 2^256)."""
    bit_w = PROF.bits
    # spread limbs to bits then regroup — static shapes, vector ops only
    shifts = jnp.arange(bit_w, dtype=jnp.int32)
    bits = (limbs[..., :, None] >> shifts) & 1  # (..., 22, 12)
    bits = bits.reshape(limbs.shape[:-1] + (PROF.n_limbs * bit_w,))[..., :256]
    if sign is not None:
        bits = bits.at[..., 255].add(sign)  # top bit is 0 for canonical y < p
    byte_shifts = jnp.arange(8, dtype=jnp.int32)
    by = bits.reshape(bits.shape[:-1] + (32, 8))
    return jnp.sum(by << byte_shifts, axis=-1).astype(jnp.uint8)


def pack_scalar_bytes_le(limbs: jnp.ndarray) -> jnp.ndarray:
    """Canonical scalar limbs → (..., 32) uint8 little-endian."""
    return _pack_bytes_le(limbs)
