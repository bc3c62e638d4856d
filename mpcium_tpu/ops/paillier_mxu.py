"""Batched Paillier on the MXU kernels (the GG18 signing hot path).

Three measured-cost optimizations over core.paillier.PaillierBatch (which
drives the generic 11-bit einsum path with full-width exponents):

1. **Short-randomizer encryption.** Enc(m; r) = (1+mN)·r^N mod N² costs a
   2048-bit exponentiation. Fix a random unit y at key load and precompute
   h = y^N mod N²; then for a short uniform u (2·security = 256 bits),
   r = y^u and r^N = h^u — both 256-bit FIXED-BASE exponentiations
   (comb tables, one mulmod per 4-bit window ⇒ 64 + 64 mulmods instead of
   ~3000). Statistically the randomizer ranges over a 2^256-size subgroup
   of the units: ciphertext indistinguishability follows from DCR + the
   standard short-exponent assumption; the MtA/range-proof algebra is
   unchanged because the proofs only ever use the VALUE r = y^u mod N.
2. **CRT decryption.** Dec(c) works mod p² and q² (2048-bit contexts, half
   the limb width of N²) with 1024-bit constant exponents p-1, q-1, then a
   CRT combine mod q — ~3× cheaper than c^λ mod N².
3. **All multiplies ride ops.modmul** (MXU Toeplitz const-muls, lookahead
   carries).

Reference correspondence: tss-lib's paillier.{EncryptAndReturnRandomness,
Decrypt} under the GG18 rounds (SURVEY.md §2.3); the per-session Go path
becomes one fused dispatch over the session batch.

Security note (SECURITY.md "Cryptographic assumptions of the batched
engine"): the short-randomizer optimization adds a short-exponent/
subgroup-sampling assumption on top of DCR. ``MPCIUM_PAILLIER_RAND_BITS``
widens the exponent (e.g. 2176 ≥ |N|+128 for statistical uniformity over
⟨y⟩); the per-session protocol path keeps reference-equivalent uniform
randomizers.
"""
from __future__ import annotations

import os
import secrets
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import bignum as bn
from ..core.paillier import PaillierPrivateKey, PaillierPublicKey
from . import modmul as mm

# short-randomizer exponent width (2 x 128-bit security); widen via env to
# trade speed for a weaker sampling assumption (SECURITY.md)
RAND_BITS = int(os.environ.get("MPCIUM_PAILLIER_RAND_BITS", "256"))


class PaillierMXU:
    """Batched Paillier for one public key over a session axis."""

    def __init__(self, pk: PaillierPublicKey, y: Optional[int] = None,
                 rng=secrets):
        self.pk = pk
        self.ctx_N = mm.MXUBarrett(pk.N)
        self.ctx_N2 = mm.MXUBarrett(pk.N2)
        self.prof_n = self.ctx_N.prof
        self.prof_n2 = self.ctx_N2.prof
        # short-randomizer base: y uniform unit mod N (gcd≠1 ⇒ factoring N)
        self.y = y if y is not None else (rng.randbelow(pk.N - 2) + 2)
        self.h = pow(self.y, pk.N, pk.N2)
        self._N_T = mm._const_matrices(pk.N, self.prof_n.n_limbs)
        # the two fixed bases, by name (ops.modmul: named operands)
        self.ctx_N2.name_comb("h", self.h, RAND_BITS)
        # y also carries the range proofs' randomizer leg, whose exponent
        # u·e + u' is 2·RAND_BITS + 8 bits in whole 7-bit limbs
        self.ctx_N.name_comb(
            "y", self.y % pk.N, -(-(2 * RAND_BITS + 8) // 7) * 7
        )

    # -- pytree: an argument of the jitted GG18 round programs --------------

    def _tree_flatten(self):
        return (self.ctx_N, self.ctx_N2, self._N_T), ()

    @classmethod
    def _tree_unflatten(cls, _aux, children):
        self = object.__new__(cls)
        self.ctx_N, self.ctx_N2, self._N_T = children
        self.prof_n = self.ctx_N.prof
        self.prof_n2 = self.ctx_N2.prof
        return self

    # -- host <-> device ----------------------------------------------------

    def to_limbs_N(self, xs) -> np.ndarray:
        return bn.batch_to_limbs(xs, self.prof_n)

    def to_limbs_N2(self, xs) -> np.ndarray:
        return bn.batch_to_limbs(xs, self.prof_n2)

    def from_limbs_N(self, arr) -> list:
        return bn.batch_from_limbs(np.asarray(arr), self.prof_n)

    def from_limbs_N2(self, arr) -> list:
        return bn.batch_from_limbs(np.asarray(arr), self.prof_n2)

    # -- kernels ------------------------------------------------------------

    def enc_deterministic(self, m_limbs: jnp.ndarray) -> jnp.ndarray:
        """(1 + m·N) mod N² for m < N (the g^m leg; exact, no reduction
        needed since (1+mN) < N²)."""
        mN = mm.carry(mm.mul_const(m_limbs, self._N_T))
        out = bn.take_limbs(mN, 0, self.prof_n2.n_limbs).at[..., 0].add(1)
        return mm.carry(out)

    def encrypt(
        self, m_limbs: jnp.ndarray, u_bits: jnp.ndarray
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """c = (1+mN)·h^u mod N², r = y^u mod N.

        ``u_bits`` (..., RAND_BITS) int32 CSPRNG bits. Returns (c, r); r is
        the effective Paillier randomizer (c == (1+mN)·r^N), which the MtA
        range proofs consume.
        """
        hu = self.ctx_N2.powmod_named_base("h", u_bits)
        c = self.ctx_N2.mulmod(self.enc_deterministic(m_limbs), hu)
        r = self.ctx_N.powmod_named_base("y", u_bits)
        return c, r

    def encrypt_many(self, pairs) -> list:
        """[(m_limbs, u_bits), ...] → [c, ...]: the encryptions of several
        plaintext batches under this key as one pass (one comb of h over
        all lanes; the randomizers' values are not computed)."""
        sizes = [m.shape[0] for m, _ in pairs]
        hu = self.ctx_N2.powmod_named_base(
            "h", jnp.concatenate([u for _, u in pairs], axis=0)
        )
        det = self.enc_deterministic(
            jnp.concatenate([m for m, _ in pairs], axis=0)
        )
        return self.ctx_N2._unstack(self.ctx_N2.mulmod(det, hu), sizes)

    def add(self, c1: jnp.ndarray, c2: jnp.ndarray) -> jnp.ndarray:
        return self.ctx_N2.mulmod(c1, c2)

    def scalar_mul(self, c: jnp.ndarray, k_bits: jnp.ndarray) -> jnp.ndarray:
        return self.ctx_N2.powmod(c, k_bits)


class PaillierMXUPrivate(PaillierMXU):
    """Adds CRT decryption (private-key holder side)."""

    def __init__(self, sk: PaillierPrivateKey, y: Optional[int] = None,
                 rng=secrets):
        super().__init__(sk.public, y=y, rng=rng)
        self.sk = sk
        p, q = sk.p, sk.q
        self.ctx_p2 = mm.MXUBarrett(p * p)
        self.ctx_q2 = mm.MXUBarrett(q * q)
        self.ctx_p = mm.MXUBarrett(p)
        self.ctx_q = mm.MXUBarrett(q)
        # L_p(x) = (x-1)/p as multiplication by p^-1 mod R^k (x-1 is an
        # exact multiple of p, so the low limbs of the product are exact)
        kp = self.ctx_p2.prof.n_limbs
        kq = self.ctx_q2.prof.n_limbs
        Rp = 1 << (mm.LIMB_BITS * kp)
        Rq = 1 << (mm.LIMB_BITS * kq)
        self._pinv_T = mm._const_matrices(pow(p, -1, Rp), kp)
        self._qinv_T = mm._const_matrices(pow(q, -1, Rq), kq)
        # h_p = L_p((1+N)^(p-1) mod p²)^-1 mod p, and mod-q twin
        def _L(x: int, r: int) -> int:
            return (x - 1) // r

        self.h_p = pow(_L(pow(1 + sk.N, p - 1, p * p), p), -1, p)
        self.h_q = pow(_L(pow(1 + sk.N, q - 1, q * q), q), -1, q)
        # CRT combine: m = m_p + p·((m_q - m_p)·p^-1 mod q)
        self.p_inv_mod_q = pow(p, -1, q)
        self._p_T_wide = mm._const_matrices(p, self.ctx_q.prof.n_limbs)
        # the key's constants as named operands: a jitted program gets
        # them as arguments, never as constants of its executable
        self.ctx_p2.name_exponent("r-1", p - 1)
        self.ctx_q2.name_exponent("r-1", q - 1)
        self.ctx_p.name_const("h_r", self.h_p)
        self.ctx_q.name_const("h_r", self.h_q)
        self.ctx_q.name_const("p_inv", self.p_inv_mod_q)

    def _tree_flatten(self):
        pub, _ = super()._tree_flatten()
        return pub + (self.ctx_p2, self.ctx_q2, self.ctx_p, self.ctx_q,
                      self._pinv_T, self._qinv_T, self._p_T_wide), ()

    @classmethod
    def _tree_unflatten(cls, _aux, children):
        self = super()._tree_unflatten((), children[:3])
        (self.ctx_p2, self.ctx_q2, self.ctx_p, self.ctx_q, self._pinv_T,
         self._qinv_T, self._p_T_wide) = children[3:]
        return self

    def _half_decrypt(self, c, ctx2, ctx1, inv_T) -> jnp.ndarray:
        """m_r = L_r(c^(r-1) mod r²)·h_r mod r → limbs in ctx1's profile."""
        u = ctx2.powmod_named_exp(ctx2.reduce(c), "r-1")
        # u - 1 via the complement trick (u-1 may have long borrow chains,
        # which the fast lookahead carry does not handle): u + (R^k - 1)
        # mod R^k == u - 1 for u ≥ 1.
        k = ctx2.prof.n_limbs
        u_minus = mm.carry(bn.pad_limbs(u + mm.MASK, 1))[..., :k]
        L = mm.carry(mm.mul_const(u_minus, inv_T))[..., :k]
        # exact division: L = (u-1)/r < r — fits the mod-r context
        return ctx1.mulmod_named(
            bn.take_limbs(L, 0, ctx1.prof.n_limbs), "h_r"
        )

    def decrypt(self, c: jnp.ndarray) -> jnp.ndarray:
        """Batched CRT decrypt → plaintext limbs mod N (prof_n)."""
        m_p = self._half_decrypt(c, self.ctx_p2, self.ctx_p, self._pinv_T)
        m_q = self._half_decrypt(c, self.ctx_q2, self.ctx_q, self._qinv_T)
        # t = (m_q - m_p) · p^-1 mod q
        nq = self.ctx_q.prof.n_limbs
        mq_q = self.ctx_q.reduce(bn.take_limbs(m_q, 0, nq))
        mp_q = self.ctx_q.reduce(bn.take_limbs(m_p, 0, nq))
        t = self.ctx_q.mulmod_named(self.ctx_q.submod(mq_q, mp_q), "p_inv")
        # m = m_p + p·t  (< p·q = N; exact, no modular reduction needed)
        pt = mm.carry(mm.mul_const(t, self._p_T_wide))
        n = self.prof_n.n_limbs
        return mm.carry(
            bn.take_limbs(pt, 0, n) + bn.take_limbs(m_p, 0, n)
        )


for _cls in (PaillierMXU, PaillierMXUPrivate):
    jax.tree_util.register_pytree_node(
        _cls, _cls._tree_flatten, _cls._tree_unflatten
    )
