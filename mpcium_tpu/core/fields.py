"""Batched prime-field arithmetic for the two curve fields.

Both curve primes are pseudo-Mersenne (p = 2^k - c with small c):
  ed25519    p = 2^255 - 19
  secp256k1  p = 2^256 - 2^32 - 977

which admits a reduction far cheaper than Barrett: limbs above the capacity
boundary fold back multiplied by ``c · 2^(capacity-k)``. Elements live in the
P256 limb profile (22 × 12-bit limbs, 264-bit capacity), normalized but *not*
canonical — values are kept in [0, 2^264) between operations and only mapped
to [0, p) by :meth:`canonical` at export/comparison points.

The scalar rings (ed25519 l, secp256k1 n) are not pseudo-Mersenne and use
``bignum.BarrettCtx`` directly.

Everything is shape-polymorphic over leading batch dimensions — this is the
per-session math that the batch engine vmaps over thousands of concurrent
wallets (SURVEY.md §2.2 "TPU mapping").
"""
from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np
from jax import lax

from . import bignum as bn
from .bignum import P256

PROF = P256


class PseudoMersenneField:
    """F_p for p = 2^k - c, elements as 22-limb int32 tensors in [0, 2^264)."""

    def __init__(self, k: int, c: int):
        assert PROF.capacity_bits >= k
        self.k = k
        self.c = c
        self.p = (1 << k) - c
        shift = PROF.capacity_bits - k  # 2^264 ≡ c · 2^shift  (mod p)
        self.fold_const = c << shift
        # fold multiplier as (short) limbs
        n_fc = max(1, -(-self.fold_const.bit_length() // PROF.bits))
        self.fc_limbs = bn.to_limbs(self.fold_const, PROF, n_limbs=n_fc)
        self.p_limbs = bn.to_limbs(self.p, PROF)
        # K·p ≥ 2^264 for borrow-free subtraction, 23 limbs
        K = (1 << shift) + 1
        self.kp_limbs = bn.to_limbs(K * self.p, PROF, n_limbs=PROF.n_limbs + 1)
        # 2^shift·p = 2^264 - c·2^shift < 2^264: the conditional-subtract
        # constant that caps fold results below capacity
        self.cap_limbs = bn.to_limbs(
            (1 << shift) * self.p, PROF, n_limbs=PROF.n_limbs + 1
        )
        # top-limb quotient estimate uses k = 21*12 + r
        self.top_shift = k - 21 * PROF.bits
        assert 0 < self.top_shift <= PROF.bits
        self.c_limbs = bn.to_limbs(self.c, PROF, n_limbs=4)

    # -- reduction ----------------------------------------------------------

    def _fold_pass(self, x: jnp.ndarray, out_width: int) -> jnp.ndarray:
        """One fold: value(x) → lo + fc·hi, carried into ``out_width`` limbs.
        Caller guarantees the folded value fits ``out_width`` limbs."""
        n = PROF.n_limbs
        lo, hi = x[..., :n], x[..., n:]
        fc = jnp.broadcast_to(
            jnp.asarray(self.fc_limbs),
            hi.shape[:-1] + (self.fc_limbs.shape[0],),
        )
        contrib = bn.mul(hi, fc, PROF)
        return bn.carry(
            bn.take_limbs(lo, 0, out_width) + bn.take_limbs(contrib, 0, out_width),
            PROF,
        )

    def fold(self, x: jnp.ndarray) -> jnp.ndarray:
        """Normalized x (any width) → congruent 22-limb value < 2^264.

        Bound accounting (fc < 2^42): a pass over w>n limbs yields
        < 2^264 + fc·2^(12(w-n)); widths shrink geometrically to n+1 limbs,
        and a final conditional subtract of 2^shift·p (< 2^264, ≥ value-2^264)
        caps the result strictly below capacity.
        """
        n = PROF.n_limbs
        while x.shape[-1] > n + 1:
            hi_limbs = x.shape[-1] - n
            contrib_limbs = hi_limbs + self.fc_limbs.shape[0]
            x = self._fold_pass(x, max(n + 1, contrib_limbs + 1))
        if x.shape[-1] == n + 1:
            x = self._fold_pass(x, n + 1)  # < 2^264 + fc·2^12 ≤ 2^264 + 2^54
            cap = jnp.broadcast_to(jnp.asarray(self.cap_limbs), x.shape)
            x = bn.cond_sub(x, cap, PROF)[..., :n]
        return x

    def mul(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        return self.fold(bn.mul(a, b, PROF))

    def square(self, a: jnp.ndarray) -> jnp.ndarray:
        return self.mul(a, a)

    def add(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        return self.fold(bn.carry(bn.pad_limbs(a + b, 1), PROF))

    def sub(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        kp = jnp.broadcast_to(
            jnp.asarray(self.kp_limbs), a.shape[:-1] + (PROF.n_limbs + 1,)
        )
        t = bn.carry(kp + bn.pad_limbs(a, 1) - bn.pad_limbs(b, 1), PROF)
        return self.fold(t)

    def neg(self, a: jnp.ndarray) -> jnp.ndarray:
        return self.sub(jnp.zeros_like(a), a)

    def mul_small(self, a: jnp.ndarray, s: int) -> jnp.ndarray:
        return self.fold(bn.mul_small(a, s, PROF))

    # -- canonical form -----------------------------------------------------

    def canonical(self, x: jnp.ndarray) -> jnp.ndarray:
        """Map [0, 2^264) → [0, p): quotient estimate + conditional subtracts."""
        n = PROF.n_limbs
        q = x[..., n - 1] >> self.top_shift  # floor(x / 2^k), ≤ 2^(264-k)
        # x ← x - q·2^k + q·c  (≡ x mod p; result < 2^k + 2^54 < 2p)
        x = x.at[..., n - 1].add(-(q << self.top_shift))
        c_l = jnp.broadcast_to(jnp.asarray(self.c_limbs), q.shape + (4,))
        qc = bn.mul(q[..., None], c_l, PROF)  # q·c ≤ 2^51, 5 limbs
        x = bn.carry(x + bn.take_limbs(qc, 0, n), PROF)
        p = jnp.broadcast_to(jnp.asarray(self.p_limbs), x.shape)
        x = bn.cond_sub(x, p, PROF)
        x = bn.cond_sub(x, p, PROF)
        return x

    def eq(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        ca, cb = self.canonical(a), self.canonical(b)
        return jnp.all(ca == cb, axis=-1)

    def is_zero(self, a: jnp.ndarray) -> jnp.ndarray:
        return jnp.all(self.canonical(a) == 0, axis=-1)

    # -- exponentiation -----------------------------------------------------

    def pow_const(self, x: jnp.ndarray, exponent: int) -> jnp.ndarray:
        if exponent == 0:
            return self.one_like(x)
        ebits = jnp.asarray(
            [(exponent >> i) & 1 for i in range(exponent.bit_length())][::-1],
            dtype=jnp.int32,
        )

        def step(acc, bit):
            acc = self.square(acc)
            acc = jnp.where(bit > 0, self.mul(acc, x), acc)
            return acc, None

        acc, _ = lax.scan(step, self.one_like(x), ebits)
        return acc

    def inv(self, x: jnp.ndarray) -> jnp.ndarray:
        """Batched inverse via Fermat. inv(0) = 0 (callers gate on is_zero)."""
        return self.pow_const(x, self.p - 2)

    # -- helpers ------------------------------------------------------------

    def one_like(self, x: jnp.ndarray) -> jnp.ndarray:
        return jnp.zeros_like(x).at[..., 0].set(1)

    def const(self, value: int, batch_shape=()) -> jnp.ndarray:
        v = jnp.asarray(bn.to_limbs(value % self.p, PROF))
        return jnp.broadcast_to(v, tuple(batch_shape) + (PROF.n_limbs,))

    def to_ints(self, x) -> list:
        return bn.batch_from_limbs(self.canonical(jnp.asarray(x)), PROF)

    def from_ints(self, xs) -> np.ndarray:
        return bn.batch_to_limbs([v % self.p for v in xs], PROF)


@functools.lru_cache(maxsize=None)
def ed25519_field() -> PseudoMersenneField:
    return PseudoMersenneField(k=255, c=19)


# a chain's registers: x, the powers x^(2^n − 1) that later runs multiply
# by, the running value and the accumulator a run squares into
_X, _X2, _X3, _X22, _X44, _T, _ACC = range(7)


def _secp256k1_chain(tail) -> np.ndarray:
    """An addition chain for a secp256k1 exponent as a straight-line
    program: rows (i, j, k), meaning r[k] = r[i]·r[j] over the seven
    registers above, which all hold x at the start. The trunk is
    libsecp256k1's (`fe_inv`, `fe_sqrt`): x^(2^n − 1) for n = 2, 3, 6, 9,
    11, 22, 44, 88, 176, 220, 223, then 23 squarings and x22, which spells
    the exponent's upper 246 bits; ``tail`` is the runs (squarings,
    register multiplied in, or None) that spell its low ten. A run squares
    into the accumulator, so the register it started from is whole at its
    end; the result is in register T."""
    prog = []

    def run(src, n, by, dst):  # r[dst] = r[src]^(2^n) · r[by]
        for _ in range(n):
            prog.append((src, src, _ACC))
            src = _ACC
        if by is None:
            prog[-1] = prog[-1][:2] + (dst,)
        else:
            prog.append((src, by, dst))

    run(_X, 1, _X, _X2)
    run(_X2, 1, _X, _X3)
    run(_X3, 3, _X3, _T)  # x6
    run(_T, 3, _X3, _T)  # x9
    run(_T, 2, _X2, _T)  # x11
    run(_T, 11, _T, _X22)
    run(_X22, 22, _X22, _X44)
    run(_X44, 44, _X44, _T)  # x88
    run(_T, 88, _T, _T)  # x176
    run(_T, 44, _X44, _T)  # x220
    run(_T, 3, _X3, _T)  # x223
    run(_T, 23, _X22, _T)
    for n, by in tail:
        run(_T, n, by, _T)
    return np.asarray(prog, np.int32)


class Secp256k1Field(PseudoMersenneField):
    """F_p for p = 2^256 − 2^32 − 977, with its two constant powers (the
    inverse and the square root) by addition chains: 255 squarings and 15
    products for p − 2, 253 and 13 for (p + 1)/4, where square-and-multiply
    over the exponent's bits (:meth:`pow_const`, 512 products) is nearly
    twice the chain of field operations. A chain runs as ONE `lax.scan`
    over its straight-line program, so a compiled program holds one
    multiplication a power however long the chain."""

    _INV = _secp256k1_chain([(5, _X), (3, _X2), (2, _X)])
    _SQRT = _secp256k1_chain([(6, _X2), (2, None)])

    def __init__(self):
        super().__init__(k=256, c=(1 << 32) + 977)

    def _chain(self, x: jnp.ndarray, prog: np.ndarray) -> jnp.ndarray:
        def step(regs, row):
            a = lax.dynamic_index_in_dim(regs, row[0], 0, keepdims=False)
            b = lax.dynamic_index_in_dim(regs, row[1], 0, keepdims=False)
            return lax.dynamic_update_index_in_dim(
                regs, self.mul(a, b), row[2], 0
            ), None

        regs = jnp.broadcast_to(x, (_ACC + 1,) + x.shape)
        regs, _ = lax.scan(step, regs, jnp.asarray(prog))
        return regs[_T]

    def inv(self, x: jnp.ndarray) -> jnp.ndarray:
        """x^(p − 2): the inverse, inv(0) = 0 (callers gate on is_zero)."""
        return self._chain(x, self._INV)

    def sqrt_candidate(self, x: jnp.ndarray) -> jnp.ndarray:
        """x^((p + 1)/4): a square root of x if it has one (p ≡ 3 mod 4)."""
        return self._chain(x, self._SQRT)


@functools.lru_cache(maxsize=None)
def secp256k1_field() -> Secp256k1Field:
    return Secp256k1Field()


class Ed25519Sqrt:
    """Square roots in F_p for p ≡ 5 (mod 8): candidate x^((p+3)/8),
    corrected by sqrt(-1) when needed. Returns (root, exists_mask)."""

    def __init__(self):
        self.F = ed25519_field()
        p = self.F.p
        self.sqrt_m1 = pow(2, (p - 1) // 4, p)

    def sqrt(self, x: jnp.ndarray):
        F = self.F
        cand = F.pow_const(x, (F.p + 3) // 8)
        c2 = F.square(cand)
        need_fix = ~F.eq(c2, x)
        fixed = F.mul(cand, F.const(self.sqrt_m1, x.shape[:-1]))
        root = jnp.where(need_fix[..., None], fixed, cand)
        ok = F.eq(F.square(root), x)
        return root, ok


class Secp256k1Sqrt:
    """Square roots in F_p for p ≡ 3 (mod 4): x^((p+1)/4)."""

    def __init__(self):
        self.F = secp256k1_field()

    def sqrt(self, x: jnp.ndarray):
        F = self.F
        root = F.sqrt_candidate(x)
        ok = F.eq(F.square(root), x)
        return root, ok
