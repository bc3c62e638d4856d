"""Batched GG18 threshold-ECDSA signing: the secp256k1 execution engine.

The north-star path (SURVEY.md §6: batched 2-of-3 secp256k1 signing): B
concurrent sessions' round compute coalesced into fixed-shape device
dispatches per party. The protocol is mathematically identical to
``protocol.ecdsa.signing`` (GG18: MtA with range proofs, phase-5
commit–reveal) — re-expressed over limb tensors:

- curve ops ride :mod:`core.secp256k1_jax` (12-bit limb family);
- Paillier / ring-Pedersen arithmetic rides :mod:`ops.modmul` (7-bit limb
  family: MXU Toeplitz constant-muls, lookahead carries) via
  :mod:`ops.paillier_mxu` (short-randomizer encryption, CRT decryption);
- hashing (commitments, Fiat–Shamir challenges) runs ON DEVICE
  (:mod:`ops.sha256`) over fixed-width byte serializations — no host
  round-trips inside the protocol (the host orchestrates dispatches only).

Quorum size is generic: ``party_ids`` may list any t+1-of-n quorum
(reference signs with any quorum ≥ t+1, ecdsa_signing_session.go:96-139);
MtA runs over all ordered pairs.

Transcript note: the batched fabric hashes fixed-width byte encodings (not
the per-session host protocol's length-prefixed ints) — the two paths are
separate wire universes; parity with the reference is at the result level
(signatures verify under the same pubkeys).

Randomness policy: a value mod M is sampled as CSPRNG bits of
``bits(M) - 8`` (for masks, where slight undersampling only strengthens the
bound) or reduced mod M on device. Paillier randomizers are y^u for
256-bit u (ops.paillier_mxu short-randomizer encryption — DCR + standard
short-exponent assumption).

Test note: proof-equation algebra holds for any key size, so unit tests run
512-bit keys with shrunk exponent domains (the ``bits`` knobs below); the
full-size path is exercised by bench.py and the slow-marked
test_gg18_full_size.
"""
from __future__ import annotations

import functools
import os
import secrets
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from ..core import bignum as bn
from ..core import hostmath as hm
from ..core import secp256k1_jax as sp
from ..core.bignum import P256
from ..core.fields import secp256k1_field
from ..core.paillier import PreParams
from ..ops import modmul as mm
from ..ops.paillier_mxu import RAND_BITS, PaillierMXUPrivate
from ..ops.sha256 import sha256 as dev_sha256
from ..perf import compile_watch
from ..protocol.base import KeygenShare, party_xs
from ..utils import log, tracing


def _trace_sync(tensors) -> None:
    """Phase-boundary sync for mpctrace/bench phase timers — reached only
    when tracing is armed or a phase_times dict was requested."""
    jax.block_until_ready(tensors)  # mpcflow: host-ok — trace/bench instrumentation, only when tracing or phase_times is requested

Q = hm.SECP_N
SCALAR_BITS = 256

# Randomized batch verification (Bellare–Garay–Rabin small-exponent test)
# for the s^N ciphertext legs: instead of one 2048-bit-exponent modexp per
# session per leg (~2560 sequential mulmod steps over the batch), the
# verifier samples per-session 128-bit ρ_b and checks ONE combined
# equation, using Π_b s_b^{ρ_b·N} = (Π_b s_b^{ρ_b})^N and
# Π_b (1+s1_b·N)^{ρ_b} = 1 + (Σ_b ρ_b·s1_b)·N mod N². Per-element cost
# drops to one 128-bit modexp (+ log-depth folds + one single-value host
# modexp). On combined-check failure the verifier falls back to strict
# per-session verification, so a bad proof is still attributed to its
# session (identifiable abort). Soundness: 2^-128 for deviations of odd
# order in Z_{N²}*; see SECURITY.md for the even-order caveat.
# MPCIUM_BATCH_VERIFY=strict restores reference-equivalent per-session
# verification.
BATCH_VERIFY = os.environ.get("MPCIUM_BATCH_VERIFY", "rand")
RHO_BITS = 128


def _fold_add(x: jnp.ndarray, extra_limbs: int = 3) -> jnp.ndarray:
    """Σ over the batch axis of normalized 7-bit limb tensors → (1, n+extra)
    normalized limbs. Exact while B·127 < 2²⁴ (B ≤ ~131k)."""
    assert x.shape[0] <= (1 << 17)
    x = bn.pad_limbs(x, extra_limbs)
    return mm.carry(jnp.sum(x, axis=0, keepdims=True))


def _host_pow_single(x_limbs: jnp.ndarray, exp: int, ctx) -> int:
    """(1, n) limbs → x^exp mod ctx.modulus via one host bigint modexp
    (a single 2048-bit-exponent value: device scan would serialize ~2.5k
    tiny dispatches; CPython pow is milliseconds)."""
    v = bn.batch_from_limbs(np.asarray(x_limbs), ctx.prof)[0]
    return pow(v, exp, ctx.modulus)


def agg_holds(alice: "PartyCtx", agg) -> bool:
    """The combined ciphertext equation of a batch-verified proof,
    settled on the host: E · S^N == R mod N² for the three single values
    a ``*_dev`` check returns."""
    n2 = alice.pmx.ctx_N2
    E, R = (
        bn.batch_from_limbs(np.asarray(x), n2.prof)[0]  # mpcflow: host-ok — single aggregated proof verdict gates the strict fallback
        for x in (agg[0], agg[2])
    )
    return E * _host_pow_single(agg[1], alice.N, n2) % n2.modulus == R


def _host_pow_batch(x_limbs: jnp.ndarray, exp: int, ctx) -> jnp.ndarray:
    """(B, n) limbs → x^exp per element on HOST. Only the strict-fallback
    (attack/abort-attribution) path uses this: the full-width-exponent
    device kernel it replaces is exactly the executable that crashes XLA's
    CPU AOT cache serializer on this class of host, and the fallback is
    cold by construction."""
    vals = bn.batch_from_limbs(np.asarray(x_limbs), ctx.prof)
    return jnp.asarray(
        bn.batch_to_limbs([pow(v, exp, ctx.modulus) for v in vals], ctx.prof)
    )


@dataclass(frozen=True)
class Domains:
    """Exponent-domain bit sizes (GG18 appendix A). Shrunk in unit tests."""

    scalar: int = 256       # curve scalars (a, b, e)
    alpha: int = 760        # < q³
    beta_prime: int = 1272  # < q⁵
    gamma_bob: int = 1784   # < q⁷
    rho_extra: int = 248    # ρ < q·NTilde  → scalar-8 + nt bits
    s1_bound: int = 768     # q³ bound checked by verifiers

    def q3(self) -> int:
        return Q**3


def _prof7(bits: int) -> bn.LimbProfile:
    """Unpadded 7-bit profile (proof-domain integers; widths stay exact so
    serializations are minimal)."""
    return bn.LimbProfile(bits=7, n_limbs=max(2, -(-bits // 7)))


def rand_bits(batch: int, bits: int, rng=secrets) -> np.ndarray:
    """(B, ceil(bits/8)) CSPRNG bytes encoding a uniform `bits`-bit int."""
    nbytes = -(-bits // 8)
    raw = np.frombuffer(rng.token_bytes(batch * nbytes), dtype=np.uint8)
    out = raw.reshape(batch, nbytes).copy()
    extra = 8 * nbytes - bits
    if extra:
        out[:, -1] &= (1 << (8 - extra)) - 1
    return out


def rand_bit_array(batch: int, bits: int, rng=secrets) -> np.ndarray:
    """(B, bits) int32 uniform CSPRNG bits, LSB-first per value (host)."""
    by = rand_bits(batch, bits, rng)
    arr = np.unpackbits(by, axis=-1, bitorder="little")[:, :bits]
    return arr.astype(np.int32)


def rand_bit_tensor(batch: int, bits: int, rng=secrets) -> jnp.ndarray:
    """:func:`rand_bit_array`, placed on the device."""
    return jnp.asarray(rand_bit_array(batch, bits, rng))


def dev_hash(tag: bytes, *rows) -> jnp.ndarray:
    """Batched SHA-256 on device over tag ‖ fixed-width rows → (B, 32)."""
    rows = [jnp.asarray(r).astype(jnp.uint8) for r in rows]
    B = rows[0].shape[0]
    t = np.frombuffer(b"mpcium-tpu/gg18-batch/" + tag, dtype=np.uint8)
    tag_t = jnp.broadcast_to(jnp.asarray(t), (B, t.shape[0]))
    return dev_sha256(jnp.concatenate([tag_t] + rows, axis=-1))


def bytes_to_bits(b: jnp.ndarray, n_bits: int) -> jnp.ndarray:
    """(..., nB) uint8 little-endian → (..., n_bits) int32 bits LSB-first."""
    bits = (b[..., :, None].astype(jnp.int32) >> jnp.arange(8)) & 1
    bits = bits.reshape(b.shape[:-1] + (b.shape[-1] * 8,))
    if bits.shape[-1] < n_bits:
        return jnp.pad(
            bits, [(0, 0)] * (bits.ndim - 1) + [(0, n_bits - bits.shape[-1])]
        )
    return bits[..., :n_bits]


def _bits_of(x: jnp.ndarray, prof: bn.LimbProfile, n_bits: int) -> jnp.ndarray:
    return bn.limbs_to_bits(x, prof, n_bits)


@functools.partial(jax.jit, static_argnums=3)
def _int_mul_add(e, m, add, prof) -> jnp.ndarray:
    """e·m + add over plain integers (no modulus), normalized to the width
    of `prof`. Inputs normalized 7-bit limbs."""
    prod = mm.mul_pair(e, m)
    width = prof.n_limbs
    return mm.carry(
        bn.take_limbs(prod, 0, width) + bn.take_limbs(add, 0, width)
    )


def _eq_all(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(a == b, axis=-1)


# ---------------------------------------------------------------------------
# per-party static contexts
# ---------------------------------------------------------------------------


class PartyCtx:
    """One signer's static crypto material + device contexts.

    The normal constructor holds the party's PRIVATE material (own
    PreParams). :meth:`public` builds a peer's context from the public
    material exchanged at keygen (peer_paillier / peer_ring_pedersen in
    the share aux) — everything MtaBatch needs from the *other* side of a
    pair: encryption under the peer's N (with a locally-chosen randomizer
    base y), ring-Pedersen commitments in the peer's NTilde, and the
    verification contexts. Decryption obviously stays private-only.
    """

    def __init__(
        self,
        pid: str,
        pre: Optional[PreParams],
        rng=secrets,
        *,
        public_material: Optional[Tuple[int, int, int, int]] = None,
    ):
        self.pid = pid
        self.pre = pre
        if public_material is not None:
            if pre is not None:
                raise ValueError("pass private PreParams OR public material")
            from ..core.paillier import PaillierPublicKey
            from ..ops.paillier_mxu import PaillierMXU

            N, NTilde, h1, h2 = public_material
            self.pmx = PaillierMXU(PaillierPublicKey(N), rng=rng)
            self._common(N, NTilde, h1, h2)
        else:
            if pre is None:
                raise ValueError("private PartyCtx requires PreParams")
            self.pmx = PaillierMXUPrivate(pre.paillier, rng=rng)
            self._common(pre.paillier.N, pre.NTilde, pre.h1, pre.h2)

    @classmethod
    def public(
        cls, pid: str, N: int, NTilde: int, h1: int, h2: int, rng=secrets
    ) -> "PartyCtx":
        return cls(pid, None, rng, public_material=(N, NTilde, h1, h2))

    def _common(self, N: int, NTilde: int, h1: int, h2: int) -> None:
        self.N = N
        self.NTilde = NTilde
        self.ctx_nt = mm.MXUBarrett(NTilde)
        self.h1 = h1
        self.h2 = h2
        self.nt_bits = NTilde.bit_length()
        self.nt_bytes = -(-NTilde.bit_length() // 8)
        self.n2_bytes = -(-(2 * N.bit_length()) // 8)
        self.n_bytes = -(-N.bit_length() // 8)

    # -- pytree: a party's context is an ARGUMENT of the jitted round
    # programs (its arrays the operands, its widths the only statics), so
    # one executable serves every key of a width and none holds a key -----

    def _tree_flatten(self):
        return (self.pmx, self.ctx_nt), (
            self.nt_bits, self.nt_bytes, self.n2_bytes, self.n_bytes,
        )

    @classmethod
    def _tree_unflatten(cls, aux, children):
        self = object.__new__(cls)
        self.pmx, self.ctx_nt = children
        self.nt_bits, self.nt_bytes, self.n2_bytes, self.n_bytes = aux
        self.pid = self.pre = self.N = self.NTilde = None
        self.h1 = self.h2 = None  # inside a trace: the named combs only
        return self

    def name_ring_combs(self, h1_bits: int, h2_bits: int) -> None:
        """Keep the combs of h1 and h2 for exponents of up to these widths
        (the proof domains decide them: MtaBatch asks)."""
        if self.h1 is None:
            return  # rebuilt inside a trace: its combs came as operands
        self.ctx_nt.name_comb("h1", self.h1, h1_bits)
        self.ctx_nt.name_comb("h2", self.h2, h2_bits)

    def commit_ring(self, m_bits: jnp.ndarray, r_bits: jnp.ndarray) -> jnp.ndarray:
        """h1^m · h2^r mod NTilde — two comb-table fixed-base exps."""
        a = self.ctx_nt.powmod_named_base("h1", m_bits)
        b = self.ctx_nt.powmod_named_base("h2", r_bits)
        return self.ctx_nt.mulmod(a, b)

    def commit_ring_many(self, pairs) -> list:
        """[(m_bits, r_bits), ...] → [h1^m · h2^r, ...]: the h1 legs as one
        comb pass over all lanes, the h2 legs as another, one product."""
        nt = self.ctx_nt
        a = nt.powmod_named_base_many("h1", [m for m, _ in pairs])
        b = nt.powmod_named_base_many("h2", [r for _, r in pairs])
        return nt.mulmod_many(list(zip(a, b)))

    def nt_row(self, x: jnp.ndarray) -> jnp.ndarray:
        return bn.limbs_to_bytes_le(x, self.ctx_nt.prof, self.nt_bytes)

    def n2_row(self, x: jnp.ndarray) -> jnp.ndarray:
        return bn.limbs_to_bytes_le(x, self.pmx.prof_n2, self.n2_bytes)


# ---------------------------------------------------------------------------
# batched MtA with range proofs (one ordered direction Alice → Bob → Alice)
# ---------------------------------------------------------------------------


class MtaBatch:
    """Batched MtA + proofs for the ordered pair (alice, bob).

    The flow mirrors protocol.ecdsa.{mta,zk} exactly, over MXU limb
    tensors with device-side Fiat–Shamir. State dicts hold limb tensors;
    every heavy call runs through jitted kernels.
    """

    def __init__(self, alice: PartyCtx, bob: PartyCtx, dom: Domains = Domains()):
        self.alice = alice
        self.bob = bob
        self.dom = dom
        d = dom
        self.p_e = _prof7(d.scalar)
        self.p_alpha = _prof7(d.alpha)
        self.p_s1 = _prof7(d.scalar + d.alpha + 7)
        nt_bits = bob.nt_bits
        nt_bits_a = alice.nt_bits
        self.p_rho = _prof7(d.scalar + max(nt_bits, nt_bits_a) + d.rho_extra)
        self.p_s2 = _prof7(d.scalar + self.p_rho.n_limbs * 7 + 7)
        self.p_bp = _prof7(d.beta_prime)
        self.p_gb = _prof7(d.gamma_bob)
        self.p_t1 = _prof7(d.scalar + d.gamma_bob + 7)
        # (a context built with its combs at these widths or wider is
        # left as it is: protocol/ecdsa/batch_signing.ContextCache)
        for side in (alice, bob):
            side.name_ring_combs(
                *self.ring_comb_bits(d, max(nt_bits, nt_bits_a)))

    @staticmethod
    def ring_comb_bits(d: Domains, nt_bits: int) -> Tuple[int, int]:
        """The widest exponent each ring-Pedersen base (h1, h2) meets in a
        pair whose wider NTilde has ``nt_bits`` bits."""
        p_s1 = _prof7(d.scalar + d.alpha + 7)
        p_t1 = _prof7(d.scalar + d.gamma_bob + 7)
        p_rho = _prof7(d.scalar + nt_bits + d.rho_extra)
        p_s2 = _prof7(d.scalar + p_rho.n_limbs * 7 + 7)
        h1_bits = max(d.scalar, d.alpha, d.beta_prime, d.gamma_bob,
                      p_s1.n_limbs * 7, p_t1.n_limbs * 7)
        return h1_bits, max(p_rho.n_limbs, p_s2.n_limbs) * 7

    def _tree_flatten(self):
        return (self.alice, self.bob), self.dom

    @classmethod
    def _tree_unflatten(cls, dom, children):
        return cls(children[0], children[1], dom)

    # -- randomness bundles (host CSPRNG → device) --------------------------

    # Each draw is (name, bits, profile): uniform CSPRNG bytes from the
    # host, in this order; profile None marks an Enc randomizer, drawn as
    # a (B, RAND_BITS) bit tensor. ``*_raw`` draws; ``randoms_from`` turns
    # the bytes into limbs and is traceable, so a jitted round program
    # takes the raw draw and no small program runs between the two.

    def _alice_draws(self):
        d, nt_b = self.dom, self.bob.nt_bits
        return (
            ("u_enc", RAND_BITS, None),  # Enc(α) randomizer
            ("alpha", d.alpha - 8, self.p_alpha),
            ("rho", d.scalar + nt_b - 8, self.p_rho),
            ("gamma", d.alpha + nt_b - 8, self.p_s2),
        )

    def _bob_draws(self):
        d, nt_a = self.dom, self.alice.nt_bits
        return (
            ("beta_prime", d.beta_prime - 8, self.p_bp),
            ("u_bp", RAND_BITS, None),  # Enc(β′) randomizer
            ("alpha", d.alpha - 8, self.p_alpha),
            ("rho", d.scalar + nt_a - 8, self.p_rho),
            ("rho_p", d.alpha + nt_a - 8, self.p_s2),
            ("sigma", d.scalar + nt_a - 8, self.p_rho),
            ("tau", d.alpha + nt_a - 8, self.p_s2),
            ("u_g", RAND_BITS, None),  # Enc(γ) randomizer
            ("gamma", d.gamma_bob - 8, self.p_gb),
        )

    @staticmethod
    def _draw(draws, B: int, rng) -> Dict[str, np.ndarray]:
        return {
            name: (rand_bit_array(B, bits, rng) if prof is None
                   else rand_bits(B, bits, rng))
            for name, bits, prof in draws
        }

    @staticmethod
    def _limbs(draws, raw) -> Dict[str, jnp.ndarray]:
        return {
            name: (jnp.asarray(raw[name]) if prof is None
                   else bn.bytes_to_limbs_le(
                       jnp.asarray(raw[name]), prof, prof.n_limbs))
            for name, _bits, prof in draws
        }

    def alice_raw(self, B: int, rng=secrets) -> Dict[str, np.ndarray]:
        return self._draw(self._alice_draws(), B, rng)

    def bob_raw(self, B: int, rng=secrets) -> Dict[str, np.ndarray]:
        return self._draw(self._bob_draws(), B, rng)

    def alice_randoms_from(self, raw) -> Dict[str, jnp.ndarray]:
        return self._limbs(self._alice_draws(), raw)

    def bob_randoms_from(self, raw) -> Dict[str, jnp.ndarray]:
        return self._limbs(self._bob_draws(), raw)

    def alice_randoms(self, B: int, rng=secrets) -> Dict[str, jnp.ndarray]:
        return self.alice_randoms_from(self.alice_raw(B, rng))

    def bob_randoms(self, B: int, rng=secrets) -> Dict[str, jnp.ndarray]:
        return self.bob_randoms_from(self.bob_raw(B, rng))

    # -- Alice: range proof for c_a = Enc_A(m; y^u) -------------------------

    def alice_init(self, m_limbs, R: Dict[str, jnp.ndarray]):
        """m: plaintext (< q) in Alice's prof_n. Returns the pre-challenge
        transcript {z, u, w} (c_a itself is per-party, passed separately).
        """
        A, Bo = self.alice, self.bob
        z, w = Bo.commit_ring_many([
            (_bits_of(m_limbs, A.pmx.prof_n, self.dom.scalar),
             _bits_of(R["rho"], self.p_rho, self.p_rho.n_limbs * 7)),
            (_bits_of(R["alpha"], self.p_alpha, self.dom.alpha),
             _bits_of(R["gamma"], self.p_s2, self.p_s2.n_limbs * 7)),
        ])
        u_c, _u_r = A.pmx.encrypt(
            bn.take_limbs(R["alpha"], 0, A.pmx.prof_n.n_limbs), R["u_enc"]
        )
        return {"z": z, "u": u_c, "w": w}

    def alice_challenge(self, c_a, T) -> jnp.ndarray:
        A, Bo = self.alice, self.bob
        return dev_hash(
            b"alice",
            A.n2_row(c_a),
            Bo.nt_row(T["z"]),
            A.n2_row(T["u"]),
            Bo.nt_row(T["w"]),
        )

    def e_limbs(self, e32: jnp.ndarray) -> jnp.ndarray:
        return bn.bytes_to_limbs_le(
            jnp.asarray(e32), self.p_e, self.p_e.n_limbs
        )

    def alice_finish(self, e, m_limbs, R, u_ca_bits):
        """Responses: s = y^(u_ca·e + u_enc) mod N (the randomizer leg,
        all in the exponent thanks to short-randomizer encryption);
        s1 = e·m + α; s2 = e·ρ + γ.

        ``u_ca_bits``: the 256-bit exponent that produced c_a's randomizer
        (r = y^u_ca)."""
        A = self.alice
        p_u = _prof7(RAND_BITS)
        u_ca = _bits_pack(u_ca_bits, p_u)
        u_enc = _bits_pack(R["u_enc"], p_u)
        prod = mm.mul_pair(u_ca, self.e_limbs_from(e))  # 512-bit integer
        p_E = _prof7(2 * RAND_BITS + 8)
        E = mm.carry(
            bn.take_limbs(prod, 0, p_E.n_limbs)
            + bn.take_limbs(u_enc, 0, p_E.n_limbs)
        )
        s = A.pmx.ctx_N.powmod_named_base(
            "y", _bits_of(E, p_E, p_E.n_limbs * 7)
        )
        m_e = bn.take_limbs(m_limbs, 0, self.p_e.n_limbs)
        e_l = self.e_limbs_from(e)
        s1 = _int_mul_add(
            e_l, m_e, bn.take_limbs(R["alpha"], 0, self.p_s1.n_limbs), self.p_s1
        )
        s2 = _int_mul_add(
            e_l, R["rho"], bn.take_limbs(R["gamma"], 0, self.p_s2.n_limbs),
            self.p_s2,
        )
        return {"s": s, "s1": s1, "s2": s2}

    def e_limbs_from(self, e) -> jnp.ndarray:
        """Accept either raw (B, 32) digest bytes or already-packed limbs."""
        if e.shape[-1] == 32 and e.dtype == jnp.uint8:
            return self.e_limbs(e)
        return e

    def _alice_ring_leg(self, T, P, e):
        """Bounds and ring-Pedersen leg of the Alice proof →
        ((B,) bool, the challenge's bits, s1 mod N)."""
        A, Bo = self.alice, self.bob
        e_l = self.e_limbs_from(e)
        q3 = jnp.broadcast_to(
            jnp.asarray(bn.to_limbs(self.dom.q3(), self.p_s1)), P["s1"].shape
        )
        ok = bn.compare(P["s1"], q3) <= 0
        e_bits = _bits_of(e_l, self.p_e, self.dom.scalar)
        s1_modN = A.pmx.ctx_N.reduce(
            bn.take_limbs(P["s1"], 0, min(P["s1"].shape[-1], 2 * A.pmx.prof_n.n_limbs))
        )
        lhs2 = Bo.commit_ring(
            _bits_of(P["s1"], self.p_s1, self.p_s1.n_limbs * 7),
            _bits_of(P["s2"], self.p_s2, self.p_s2.n_limbs * 7),
        )
        rhs2 = Bo.ctx_nt.mulmod(T["w"], Bo.ctx_nt.powmod(T["z"], e_bits))
        return ok & _eq_all(lhs2, rhs2), e_bits, s1_modN

    def bob_check_alice_dev(self, c_a, T, P, e, rho_bits):
        """The device part of the batched Alice-proof verification
        (traceable): → ((B,) bool of the bounds and the ring leg, and
        the three single values (E, S, R) of the combined ciphertext
        equation E · S^N == R mod N², which :func:`agg_holds` settles)."""
        ok, e_bits, s1_modN = self._alice_ring_leg(T, P, e)
        return ok, self._alice_enc_agg(c_a, T, P, e_bits, s1_modN, rho_bits)

    def bob_check_alice(self, c_a, T, P, e, rng=secrets) -> jnp.ndarray:
        """Batched Alice-proof verification → (B,) bool."""
        if BATCH_VERIFY != "rand":
            return self.bob_check_alice_strict(c_a, T, P, e)
        rho_bits = rand_bit_tensor(P["s1"].shape[0], RHO_BITS, rng)
        ok, agg = self.bob_check_alice_dev(c_a, T, P, e, rho_bits)
        if agg_holds(self.alice, agg):
            return ok
        log.warn("batched Alice-proof check failed — strict re-verification")
        return self.bob_check_alice_strict(c_a, T, P, e)

    def bob_check_alice_strict(self, c_a, T, P, e) -> jnp.ndarray:
        ok, e_bits, s1_modN = self._alice_ring_leg(T, P, e)
        return ok & self._alice_enc_leg_strict(c_a, T, P, e_bits, s1_modN)

    def _alice_enc_leg_strict(self, c_a, T, P, e_bits, s1_modN) -> jnp.ndarray:
        """Per-session ciphertext-leg check:
        Enc_det(s1)·s^N == u·c_a^e (mod N²). The s^N piece runs on host
        (see _host_pow_batch)."""
        A = self.alice
        n2 = A.pmx.ctx_N2
        lhs = n2.mulmod(
            A.pmx.enc_deterministic(s1_modN),
            _host_pow_batch(
                bn.take_limbs(P["s"], 0, n2.prof.n_limbs), A.N, n2
            ),
        )
        rhs = n2.mulmod(T["u"], n2.powmod(c_a, e_bits))
        return _eq_all(lhs, rhs)

    def _alice_enc_agg(self, c_a, T, P, e_bits, s1_modN, rho_bits):
        """Ciphertext leg of the Alice proof, batch-verified (module
        docstring at BATCH_VERIFY): Enc_det(Σρ·s1) · (Πs^ρ)^N ==
        Π(u·c_a^e)^ρ → the single values (Enc_det(Σρ·s1), Πs^ρ,
        Π(u·c_a^e)^ρ)."""
        A = self.alice
        n2 = A.pmx.ctx_N2
        rhs = n2.mulmod(T["u"], n2.powmod(c_a, e_bits))
        s2 = bn.take_limbs(P["s"], 0, n2.prof.n_limbs)
        rhs_rho, s_rho = n2.powmod_many([(rhs, rho_bits), (s2, rho_bits)])
        Rp = n2.prod_over_batch(rhs_rho)[None]
        Sp = n2.prod_over_batch(s_rho)[None]
        rho_l = _bits_pack(rho_bits, _prof7(RHO_BITS))
        tot = A.pmx.ctx_N.reduce(_fold_add(mm.mul_pair(rho_l, s1_modN)))
        return A.pmx.enc_deterministic(tot), Sp, Rp

    # -- Bob: homomorphic response + proof ----------------------------------

    def bob_respond(self, c_a, b_limbs, R):
        """c_b = c_a^b · Enc_A(β′; y^u_bp); pre-challenge proof transcript.
        ``b_limbs``: Bob's secret (< q) in the 7-bit e-profile."""
        A = self.alice
        n2 = A.pmx.ctx_N2
        nl = A.pmx.prof_n.n_limbs
        b_bits = _bits_of(b_limbs, self.p_e, self.dom.scalar)
        alpha_bits = _bits_of(R["alpha"], self.p_alpha, self.dom.alpha)
        enc_bp, enc_g = A.pmx.encrypt_many([
            (bn.take_limbs(R["beta_prime"], 0, nl), R["u_bp"]),
            (bn.take_limbs(R["gamma"], 0, nl), R["u_g"]),
        ])
        c_b, v = n2.mulmod_many(list(zip(
            n2.powmod_many([(c_a, b_bits), (c_a, alpha_bits)]),
            (enc_bp, enc_g),
        )))
        z, z_p, t, w = A.commit_ring_many([
            (b_bits, _bits_of(R["rho"], self.p_rho, self.p_rho.n_limbs * 7)),
            (alpha_bits,
             _bits_of(R["rho_p"], self.p_s2, self.p_s2.n_limbs * 7)),
            (_bits_of(R["beta_prime"], self.p_bp, self.dom.beta_prime),
             _bits_of(R["sigma"], self.p_rho, self.p_rho.n_limbs * 7)),
            (_bits_of(R["gamma"], self.p_gb, self.dom.gamma_bob),
             _bits_of(R["tau"], self.p_s2, self.p_s2.n_limbs * 7)),
        ])
        return {"c_b": c_b, "z": z, "z_p": z_p, "t": t, "v": v, "w": w}

    def bob_challenge(self, c_a, T, extra_rows: Sequence = ()) -> jnp.ndarray:
        A = self.alice
        rows = [
            A.n2_row(c_a),
            A.n2_row(T["c_b"]),
            A.nt_row(T["z"]),
            A.nt_row(T["z_p"]),
            A.nt_row(T["t"]),
            A.n2_row(T["v"]),
            A.nt_row(T["w"]),
        ]
        rows.extend(extra_rows)
        return dev_hash(b"bob", *rows)

    def bob_finish(self, e, b_limbs, R):
        A = self.alice
        e_l = self.e_limbs_from(e)
        p_u = _prof7(RAND_BITS)
        u_bp = _bits_pack(R["u_bp"], p_u)
        u_g = _bits_pack(R["u_g"], p_u)
        prod = mm.mul_pair(u_bp, e_l)
        p_E = _prof7(2 * RAND_BITS + 8)
        E = mm.carry(
            bn.take_limbs(prod, 0, p_E.n_limbs)
            + bn.take_limbs(u_g, 0, p_E.n_limbs)
        )
        s = A.pmx.ctx_N.powmod_named_base(
            "y", _bits_of(E, p_E, p_E.n_limbs * 7)
        )
        s1 = _int_mul_add(
            e_l, bn.take_limbs(b_limbs, 0, self.p_e.n_limbs),
            bn.take_limbs(R["alpha"], 0, self.p_s1.n_limbs), self.p_s1,
        )
        s2 = _int_mul_add(
            e_l, R["rho"], bn.take_limbs(R["rho_p"], 0, self.p_s2.n_limbs),
            self.p_s2,
        )
        t1 = _int_mul_add(
            e_l, bn.take_limbs(R["beta_prime"], 0, self.p_t1.n_limbs),
            bn.take_limbs(R["gamma"], 0, self.p_t1.n_limbs), self.p_t1,
        )
        t2 = _int_mul_add(
            e_l, R["sigma"], bn.take_limbs(R["tau"], 0, self.p_s2.n_limbs),
            self.p_s2,
        )
        return {"s": s, "s1": s1, "s2": s2, "t1": t1, "t2": t2}

    def _bob_ring_legs(self, c_a, T, P, e):
        """Bounds and ring-Pedersen legs of the Bob proof, and the two
        sides of its ciphertext leg less s^N →
        ((B,) bool, M = c_a^s1 · Enc_det(t1), v · c_b^e, s lifted)."""
        A = self.alice
        e_l = self.e_limbs_from(e)
        q3 = jnp.broadcast_to(
            jnp.asarray(bn.to_limbs(self.dom.q3(), self.p_s1)), P["s1"].shape
        )
        ok = bn.compare(P["s1"], q3) <= 0
        t1_cap = (1 << (self.p_t1.bits * self.p_t1.n_limbs)) - 1
        q7 = jnp.broadcast_to(
            jnp.asarray(bn.to_limbs(min(Q**7, t1_cap), self.p_t1)),
            P["t1"].shape,
        )
        ok = ok & (bn.compare(P["t1"], q7) <= 0)
        e_bits = _bits_of(e_l, self.p_e, self.dom.scalar)
        s1_bits = _bits_of(P["s1"], self.p_s1, self.p_s1.n_limbs * 7)
        nt = A.ctx_nt
        lhs_s, lhs_t = A.commit_ring_many([
            (s1_bits, _bits_of(P["s2"], self.p_s2, self.p_s2.n_limbs * 7)),
            (_bits_of(P["t1"], self.p_t1, self.p_t1.n_limbs * 7),
             _bits_of(P["t2"], self.p_s2, self.p_s2.n_limbs * 7)),
        ])
        rhs_s, rhs_t = nt.mulmod_many(list(zip(
            (T["z_p"], T["w"]),
            nt.powmod_many([(T["z"], e_bits), (T["t"], e_bits)]),
        )))
        ok = ok & _eq_all(lhs_s, rhs_s) & _eq_all(lhs_t, rhs_t)
        n2 = A.pmx.ctx_N2
        t1_modN = A.pmx.ctx_N.reduce(
            bn.take_limbs(P["t1"], 0, min(P["t1"].shape[-1], 2 * A.pmx.prof_n.n_limbs))
        )
        # ciphertext leg: c_a^s1 · Enc_det(t1) · s^N == v · c_b^e (mod N²)
        M, rhs = n2.mulmod_many(list(zip(
            n2.powmod_many([(c_a, s1_bits), (T["c_b"], e_bits)]),
            (A.pmx.enc_deterministic(t1_modN), T["v"]),
        )))
        return ok, M, rhs, bn.take_limbs(P["s"], 0, n2.prof.n_limbs)

    def alice_check_bob_dev(self, c_a, T, P, e, rho_bits):
        """The device part of the batched Bob-proof verification
        (traceable): → ((B,) bool of the bounds and the ring legs, and the
        single values (ΠM^ρ, Πs^ρ, Π(v·c_b^e)^ρ) for :func:`agg_holds`)."""
        ok, M, rhs, s_lift = self._bob_ring_legs(c_a, T, P, e)
        n2 = self.alice.pmx.ctx_N2
        Mp, Sp, Rp = (
            n2.prod_over_batch(x)[None] for x in n2.powmod_many(
                [(M, rho_bits), (s_lift, rho_bits), (rhs, rho_bits)])
        )
        return ok, (Mp, Sp, Rp)

    def alice_check_bob(self, c_a, T, P, e, rng=secrets) -> jnp.ndarray:
        """Batched Bob-proof verification (ciphertext + ring legs; the
        with-check curve leg is checked by the caller)."""
        if BATCH_VERIFY == "rand":
            rho_bits = rand_bit_tensor(P["s1"].shape[0], RHO_BITS, rng)
            ok, agg = self.alice_check_bob_dev(c_a, T, P, e, rho_bits)
            if agg_holds(self.alice, agg):
                return ok
            log.warn("batched Bob-proof check failed — strict re-verification")
        return self.alice_check_bob_strict(c_a, T, P, e)

    def alice_check_bob_strict(self, c_a, T, P, e) -> jnp.ndarray:
        ok, M, rhs, s_lift = self._bob_ring_legs(c_a, T, P, e)
        A = self.alice
        n2 = A.pmx.ctx_N2
        lhs = n2.mulmod(M, _host_pow_batch(s_lift, A.N, n2))
        return ok & _eq_all(lhs, rhs)

    def alice_decrypt_share(self, c_b) -> jnp.ndarray:
        """Dec_A(c_b) mod q → curve-scalar limbs (12-bit family)."""
        A = self.alice
        plain = A.pmx.decrypt(c_b)  # (B, n) mod N, 7-bit limbs
        return _mod_q_from_limbs(plain, A.pmx.prof_n)


@functools.partial(jax.jit, static_argnums=1)
def _bits_pack(bits: jnp.ndarray, prof: bn.LimbProfile) -> jnp.ndarray:
    """(..., n_bits) LSB-first bit tensor → normalized limbs in prof."""
    n_bits = bits.shape[-1]
    want = prof.n_limbs * prof.bits
    if n_bits < want:
        bits = jnp.pad(
            bits, [(0, 0)] * (bits.ndim - 1) + [(0, want - n_bits)]
        )
    else:
        bits = bits[..., :want]
    groups = bits.reshape(bits.shape[:-1] + (prof.n_limbs, prof.bits))
    w = 1 << jnp.arange(prof.bits, dtype=jnp.int32)
    return jnp.sum(groups * w, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# curve-side jitted helpers (12-bit family)
# ---------------------------------------------------------------------------


@jax.jit
def _scalar_from_wide_bytes(b: jnp.ndarray) -> jnp.ndarray:
    """(B, 40) uniform bytes → canonical scalar mod q (bias 2^-64)."""
    ring = sp.scalar_ring()
    return ring.reduce(bn.bytes_to_limbs_le(b, P256, 30))


@jax.jit
def _base_mul_compressed(k_limbs: jnp.ndarray):
    pt = sp.base_mul(bn.limbs_to_bits(k_limbs, P256, SCALAR_BITS))
    return pt, sp.compress(pt)


def _scalar_to_plain(pmx, k_limbs: jnp.ndarray) -> jnp.ndarray:
    """curve scalar (12-bit limbs) → Paillier plaintext limbs (7-bit)."""
    b = bn.limbs_to_bytes_le(k_limbs, P256, 32)
    return bn.bytes_to_limbs_le(b, pmx.prof_n, pmx.prof_n.n_limbs)


def _scalar_to_prof(k_limbs: jnp.ndarray, prof: bn.LimbProfile) -> jnp.ndarray:
    b = bn.limbs_to_bytes_le(k_limbs, P256, 32)
    return bn.bytes_to_limbs_le(b, prof, prof.n_limbs)


@functools.partial(jax.jit, static_argnums=1)
def _mod_q_from_limbs(x: jnp.ndarray, prof: bn.LimbProfile) -> jnp.ndarray:
    """Reduce an arbitrary-width non-negative value mod q → 12-bit curve
    limbs, via chunked folding: v = Σ chunk_i · (2^(176·i)) mod q."""
    ring = sp.scalar_ring()
    n_bytes = -(-prof.n_limbs * prof.bits // 8)
    b = bn.limbs_to_bytes_le(x, prof, n_bytes)
    chunk_bytes = 22  # 176 bits per chunk < 2^253
    n_chunks = -(-n_bytes // chunk_bytes)
    pad = n_chunks * chunk_bytes - n_bytes
    if pad:
        b = jnp.pad(b, [(0, 0)] * (b.ndim - 1) + [(0, pad)])
    chunks = b.reshape(b.shape[:-1] + (n_chunks, chunk_bytes))
    shift_l = ring.const(pow(2, chunk_bytes * 8, Q), x.shape[:-1])
    limbs = bn.bytes_to_limbs_le(chunks, P256, P256.n_limbs)

    def horner(acc, c):  # one compiled step, the most significant chunk first
        return ring.addmod(ring.mulmod(acc, shift_l), ring.reduce(c)), None

    acc, _ = lax.scan(horner, ring.const(0, x.shape[:-1]),
                      jnp.moveaxis(limbs, -2, 0), reverse=True)
    return acc


# ---------------------------------------------------------------------------
# curve-phase BLOCKS — jitted at per-party granularity so one compiled
# executable is reused q times per sign and shared across runs. (Both
# extremes failed on this host: fusing a whole phase into one jit produced
# 15+ minute XLA compiles; fully-eager execution paid ~ms of dispatch per
# primitive across tens of thousands of curve ops. Party domain separation
# rides an index-byte OPERAND, not per-party hash tags, so block HLO is
# party-independent.)
# ---------------------------------------------------------------------------


def _cat_pts(pts):
    return type(pts[0])(*(jnp.concatenate(c, axis=0) for c in zip(*pts)))


def _split_pts(pt, n: int):
    B = pt.X.shape[0] // n
    return [type(pt)(*(c[i * B:(i + 1) * B] for c in pt)) for i in range(n)]


def _scalar_mul_many(pairs):
    """[(scalar limbs, points), ...] of one lane count → [k·P, ...] as ONE
    ladder over all lanes (a ladder's time at these widths is its steps,
    not its lanes)."""
    bits = jnp.concatenate(
        [bn.limbs_to_bits(k, P256, SCALAR_BITS) for k, _ in pairs], axis=0
    )
    out = sp.scalar_mul(bits, _cat_pts([p for _, p in pairs]))
    return _split_pts(out, len(pairs))


def _compress_many(*pts):
    """Several point batches compressed with one field inversion ladder."""
    n = pts[0].X.shape[0]
    comp = sp.compress(_cat_pts(pts))
    return [comp[i * n:(i + 1) * n] for i in range(len(pts))]


def _idx_row(i: int, B: int) -> jnp.ndarray:
    return jnp.full((B, 1), i, jnp.uint8)


@jax.jit
def _blk_commit(tagged_payload_rows):
    """Generic hash commitment over pre-concatenated (B, L) uint8 rows."""
    return dev_sha256(tagged_payload_rows)


@jax.jit
def _blk_gamma(gamma_i, blind_i, idx):
    """Γ_i = γ_i·G, compressed + hash-committed (round 1, per party)."""
    pt = sp.base_mul(bn.limbs_to_bits(gamma_i, P256, SCALAR_BITS))
    comp = sp.compress(pt)
    commit = dev_hash(b"gamma", idx, blind_i, comp)
    return pt, comp, commit


@jax.jit
def _blk_gamma_check(blind_i, comp_i, idx, commit_i):
    return _eq_all(dev_hash(b"gamma", idx, blind_i, comp_i), commit_i)


@jax.jit
def _blk_point_add(a: sp.SecpPointJ, b: sp.SecpPointJ) -> sp.SecpPointJ:
    return sp.add(a, b)


@jax.jit
def _blk_point_eq(a: sp.SecpPointJ, b: sp.SecpPointJ) -> jnp.ndarray:
    return sp.equal(a, b)


@jax.jit
def _blk_R(delta, Gamma_sum):
    """δ⁻¹·ΣΓ, r = R_x mod q, recovery metadata, degeneracy flags."""
    ring = sp.scalar_ring()
    ok = ~jnp.all(delta == 0, axis=-1)
    delta_inv = ring.powmod_const(delta, Q - 2)
    R_pt = sp.scalar_mul(
        bn.limbs_to_bits(delta_inv, P256, SCALAR_BITS), Gamma_sum
    )
    F = secp256k1_field()
    zi = F.inv(R_pt.Z)  # one inversion serves both affine coordinates
    Rx = F.canonical(F.mul(R_pt.X, zi))
    r = ring.reduce(Rx)
    ok = ok & ~jnp.all(r == 0, axis=-1)
    y_aff = F.canonical(F.mul(R_pt.Y, zi))
    n_limbs_ = jnp.broadcast_to(jnp.asarray(bn.to_limbs(Q, P256)), Rx.shape)
    rec = (y_aff[..., 0] & 1) | jnp.where(bn.compare(Rx, n_limbs_) >= 0, 2, 0)
    return ok, R_pt, r, rec


@jax.jit
def _blk_schnorr(kpok_i, gamma_i, Gamma_i, comp_i, idx):
    """Batched Schnorr PoK of γ_i: prove + self-verify (honest fabric)."""
    ring = sp.scalar_ring()
    A_pt = sp.base_mul(bn.limbs_to_bits(kpok_i, P256, SCALAR_BITS))
    A_comp = sp.compress(A_pt)
    e32 = dev_hash(b"schnorr", idx, A_comp, comp_i)
    e = ring.reduce(bn.bytes_to_limbs_le(e32, P256, 22))
    s_pok = ring.submod(kpok_i, ring.mulmod(e, gamma_i))
    lhs = sp.add(
        sp.base_mul(bn.limbs_to_bits(s_pok, P256, SCALAR_BITS)),
        sp.scalar_mul(bn.limbs_to_bits(e, P256, SCALAR_BITS), Gamma_i),
    )
    return _eq_all(sp.compress(lhs), A_comp)


# -- prover/verifier split variants of the PoK blocks (the distributed
# protocol sends proofs across the transport; the in-process fabric keeps
# the fused prove+self-verify blocks above) --------------------------------


@jax.jit
def _blk_schnorr_prove(kpok_i, gamma_i, comp_i, idx):
    """Schnorr PoK of γ_i, prover side → (A_comp, s_pok)."""
    ring = sp.scalar_ring()
    A_pt = sp.base_mul(bn.limbs_to_bits(kpok_i, P256, SCALAR_BITS))
    A_comp = sp.compress(A_pt)
    e32 = dev_hash(b"schnorr", idx, A_comp, comp_i)
    e = ring.reduce(bn.bytes_to_limbs_le(e32, P256, 22))
    s_pok = ring.submod(kpok_i, ring.mulmod(e, gamma_i))
    return A_comp, s_pok


@jax.jit
def _blk_schnorr_verify(A_comp, s_pok, Gamma_i: sp.SecpPointJ, comp_i, idx):
    """Schnorr PoK verify: s·G + e·Γ ?= A → (B,) bool."""
    ring = sp.scalar_ring()
    e32 = dev_hash(b"schnorr", idx, A_comp, comp_i)
    e = ring.reduce(bn.bytes_to_limbs_le(e32, P256, 22))
    lhs = sp.add(
        sp.base_mul(bn.limbs_to_bits(s_pok, P256, SCALAR_BITS)),
        sp.scalar_mul(bn.limbs_to_bits(e, P256, SCALAR_BITS), Gamma_i),
    )
    return _eq_all(sp.compress(lhs), A_comp)


@jax.jit
def _blk_pedersen_prove(ka, kb, s_i, l_i, R_pt, vc, ac, idx):
    """Phase-5B PedersenPoK of (s_i, l_i), prover side →
    (Apok_comp, sa, sb)."""
    ring = sp.scalar_ring()
    Apok = sp.add(
        sp.scalar_mul(bn.limbs_to_bits(ka, P256, SCALAR_BITS), R_pt),
        sp.base_mul(bn.limbs_to_bits(kb, P256, SCALAR_BITS)),
    )
    Apok_comp = sp.compress(Apok)
    e32 = dev_hash(b"pedersen", idx, Apok_comp, vc, ac)
    e5 = ring.reduce(bn.bytes_to_limbs_le(e32, P256, 22))
    sa = ring.submod(ka, ring.mulmod(e5, s_i))
    sb = ring.submod(kb, ring.mulmod(e5, l_i))
    return Apok_comp, sa, sb


@jax.jit
def _blk_pedersen_verify(Apok_comp, sa, sb, V_i: sp.SecpPointJ, R_pt, vc, ac, idx):
    """Phase-5B PedersenPoK verify: sa·R + sb·G + e·V ?= Apok."""
    ring = sp.scalar_ring()
    e32 = dev_hash(b"pedersen", idx, Apok_comp, vc, ac)
    e5 = ring.reduce(bn.bytes_to_limbs_le(e32, P256, 22))
    saR, eV = _scalar_mul_many([(sa, R_pt), (e5, V_i)])
    lhs = sp.add(
        sp.add(saR, sp.base_mul(bn.limbs_to_bits(sb, P256, SCALAR_BITS))),
        eV,
    )
    return _eq_all(sp.compress(lhs), Apok_comp)


@jax.jit
def _blk_va_check(blind_i, vc, ac, idx, commit):
    """Phase-5B decommit check of a peer's (V_c, A_c) commitment."""
    return _eq_all(dev_hash(b"VA", idx, blind_i, vc, ac), commit)


@jax.jit
def _blk_va(m, r, k_i, sigma_i, l_i, rho_i, R_pt, blind_i, idx):
    """Phase 5A per party: s_i, V_i = s_i·R + l_i·G, A_i = ρ_i·G, commit."""
    ring = sp.scalar_ring()
    s_i = ring.addmod(ring.mulmod(m, k_i), ring.mulmod(r, sigma_i))
    V_i = sp.add(
        sp.scalar_mul(bn.limbs_to_bits(s_i, P256, SCALAR_BITS), R_pt),
        sp.base_mul(bn.limbs_to_bits(l_i, P256, SCALAR_BITS)),
    )
    A_i = sp.base_mul(bn.limbs_to_bits(rho_i, P256, SCALAR_BITS))
    vc, ac = _compress_many(V_i, A_i)
    commit = dev_hash(b"VA", idx, blind_i, vc, ac)
    return s_i, V_i, A_i, vc, ac, commit


@jax.jit
def _blk_pedersen(ka, kb, s_i, l_i, V_i, R_pt, vc, ac, blind_i, idx, commit):
    """Phase 5B per party: decommit check + PedersenPoK of (s_i, l_i)."""
    ring = sp.scalar_ring()
    ok = _eq_all(dev_hash(b"VA", idx, blind_i, vc, ac), commit)
    Apok = sp.add(
        sp.scalar_mul(bn.limbs_to_bits(ka, P256, SCALAR_BITS), R_pt),
        sp.base_mul(bn.limbs_to_bits(kb, P256, SCALAR_BITS)),
    )
    Apok_comp = sp.compress(Apok)
    e32 = dev_hash(b"pedersen", idx, Apok_comp, vc, ac)
    e5 = ring.reduce(bn.bytes_to_limbs_le(e32, P256, 22))
    sa = ring.submod(ka, ring.mulmod(e5, s_i))
    sb = ring.submod(kb, ring.mulmod(e5, l_i))
    lhs = sp.add(
        sp.add(
            sp.scalar_mul(bn.limbs_to_bits(sa, P256, SCALAR_BITS), R_pt),
            sp.base_mul(bn.limbs_to_bits(sb, P256, SCALAR_BITS)),
        ),
        sp.scalar_mul(bn.limbs_to_bits(e5, P256, SCALAR_BITS), V_i),
    )
    return ok & _eq_all(sp.compress(lhs), Apok_comp)


@jax.jit
def _blk_V(V_sum, m, r, Y):
    """V = ΣV_i - m·G - r·Y (phase 5C prelude)."""
    m_bits = bn.limbs_to_bits(m, P256, SCALAR_BITS)
    return sp.add(
        V_sum,
        sp.add(
            sp.neg(sp.base_mul(m_bits)),
            sp.neg(sp.scalar_mul(bn.limbs_to_bits(r, P256, SCALAR_BITS), Y)),
        ),
    )


@jax.jit
def _blk_ut(rho_i, l_i, V, A_sum, blind_i, idx):
    """Phase 5C per party: U_i = ρ_i·V, T_i = l_i·ΣA, commit."""
    U_i, T_i = _scalar_mul_many([(rho_i, V), (l_i, A_sum)])
    uc, tc = _compress_many(U_i, T_i)
    commit = dev_hash(b"UT", idx, blind_i, uc, tc)
    return U_i, T_i, uc, tc, commit


@jax.jit
def _blk_ut_check(blind_i, uc, tc, idx, commit):
    return _eq_all(dev_hash(b"UT", idx, blind_i, uc, tc), commit)


@jax.jit
def _blk_final(s, m, r, Y, rec):
    """Low-s normalize + batched ECDSA verification x(u1·G+u2·Y) == r."""
    ring = sp.scalar_ring()
    ok = ~jnp.all(s == 0, axis=-1)
    half = jnp.broadcast_to(jnp.asarray(bn.to_limbs(Q // 2, P256)), s.shape)
    high = bn.compare(s, half) > 0
    s = jnp.where(high[..., None], ring.negmod(s), s)
    rec = jnp.where(high, rec ^ 1, rec)
    s_inv = ring.powmod_const(s, Q - 2)
    u1 = ring.mulmod(m, s_inv)
    u2 = ring.mulmod(r, s_inv)
    Rv = sp.add(
        sp.base_mul(bn.limbs_to_bits(u1, P256, SCALAR_BITS)),
        sp.scalar_mul(bn.limbs_to_bits(u2, P256, SCALAR_BITS), Y),
    )
    ok = ok & jnp.all(ring.reduce(sp.x_coordinate(Rv)) == r, axis=-1)
    return ok, s, rec


@jax.jit
def _withcheck_curve(s1_q, e_q, U_pt, W_pt):
    """MtAwc curve binding: s1·G ?= U + e·W → (B,) bool."""
    lhs = sp.base_mul(bn.limbs_to_bits(s1_q, P256, SCALAR_BITS))
    rhs = sp.add(
        U_pt,
        sp.scalar_mul(bn.limbs_to_bits(e_q, P256, SCALAR_BITS), W_pt),
    )
    return sp.equal(lhs, rhs)


# ---------------------------------------------------------------------------
# ROUND PROGRAMS of the distributed party (protocol.ecdsa.batch_signing).
#
# One jitted program per protocol step: wire blocks come in and go out as
# (B, n) uint8 arrays, party contexts (PartyCtx / MtaBatch) are pytree
# ARGUMENTS, and everything between (parsing, bit unpacking, slicing,
# hashing, the ladders and the modular exponentiations, serialization) is
# inside the one program. A served wave therefore runs a fixed, small set
# of programs per node and asks XLA for none after the warm batch; the
# eager per-op dispatches the party used to make between kernels (a
# compile request and a program run each) are gone. Peers' blocks of a
# broadcast round arrive stacked on a leading axis and are verified as one
# (q-1)·B-lane batch. The programs are named ``gg18_*``: a device trace
# shows them as ``jit_gg18_*`` (benchmark/, PERF.md).
# ---------------------------------------------------------------------------


def wire_bytes(prof: bn.LimbProfile) -> int:
    """Bytes of a limb tensor's wire block row."""
    return -(-prof.n_limbs * prof.bits // 8)


def _wire(x: jnp.ndarray, prof: bn.LimbProfile) -> jnp.ndarray:
    return bn.limbs_to_bytes_le(x, prof, wire_bytes(prof))


def _unwire(b: jnp.ndarray, prof: bn.LimbProfile) -> jnp.ndarray:
    return bn.bytes_to_limbs_le(b, prof, prof.n_limbs)


def _scalar_be(b: jnp.ndarray) -> jnp.ndarray:
    """(…, 32) big-endian bytes → scalar limbs mod q."""
    return sp.scalar_ring().reduce(
        bn.bytes_to_limbs_le(jnp.flip(b, axis=-1), P256, 22)
    )


def _flat(x: jnp.ndarray) -> jnp.ndarray:
    """(P, B, …) → (P·B, …): the peers' blocks as one batch."""
    return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])


def _tile_pt(pt, n: int):
    """A (B, …) point pytree repeated for ``n`` peers → (n·B, …)."""
    return type(pt)(*(jnp.tile(leaf, (n,) + (1,) * (leaf.ndim - 1))
                      for leaf in pt))


def _sum_pts_many(terms, n: int):
    """[(own, flat peers), ...] → [own + Σ over the n peers of a (n·B, …)
    point batch, ...]: the sums side by side on the lane axis, the peers
    one after another through ONE compiled addition."""
    B = terms[0][0].X.shape[0]
    own = _cat_pts([o for o, _ in terms])
    peers = type(own)(*(
        jnp.concatenate(
            [c.reshape((n, B) + c.shape[1:]) for c in coords], axis=1)
        for coords in zip(*(f for _, f in terms))
    ))
    acc, _ = lax.scan(lambda acc, p: (sp.add(acc, p), None), own, peers)
    return _split_pts(acc, len(terms))


def _sum_pts(own, flat_peers, n: int):
    return _sum_pts_many([(own, flat_peers)], n)[0]


@jax.jit
def gg18_setup(pub_comp, C_comp, digests, x_bits, lam_bits):
    """What a batch keeps on the device from its public inputs: the
    wallets' keys Y, every quorum member's W_j = λ_j · Σ_k x_j^k · C_k
    (all members as one q·B-lane ladder) with its compressed form, the
    digests as scalars, and the lanes whose encodings were sound.

    ``C_comp`` (t+1, B, 33) Feldman commitments, ``x_bits`` (q, 8) and
    ``lam_bits`` (q, 256) the members' Shamir x and Lagrange coefficient,
    LSB first: operands, so one executable serves every quorum."""
    q, B = x_bits.shape[0], pub_comp.shape[0]
    Y, ok = sp.decompress(pub_comp)
    pts, ok_c = sp.decompress(C_comp)
    ok = ok & jnp.all(ok_c, axis=0)
    t1 = C_comp.shape[0]
    xb = jnp.repeat(x_bits, B, axis=0)      # (q·B, 8)
    lb = jnp.repeat(lam_bits, B, axis=0)    # (q·B, 256)

    def coeff(k):
        return _tile_pt(sp.SecpPointJ(pts.X[k], pts.Y[k], pts.Z[k]), q)

    acc = coeff(t1 - 1)
    for k in range(t1 - 2, -1, -1):
        acc = sp.add(sp.scalar_mul(xb, acc), coeff(k))
    W = sp.scalar_mul(lb, acc)
    W_comp = sp.compress(W)
    W_pts = tuple(
        sp.SecpPointJ(*(leaf[i * B:(i + 1) * B] for leaf in W))
        for i in range(q)
    )
    W_comps = tuple(W_comp[i * B:(i + 1) * B] for i in range(q))
    return Y, W_pts, W_comps, ok, _scalar_be(digests)


@jax.jit
def gg18_r1_commit(own: PartyCtx, k_raw, gamma_raw, gblind, bind, u_bits):
    """Round 1, own side: k, γ, the Γ commitment and c = Enc(k)."""
    k = _scalar_from_wide_bytes(k_raw)
    gamma = _scalar_from_wide_bytes(gamma_raw)
    Gam, Gam_comp, commit = _blk_gamma(gamma, gblind, bind)
    kp = _scalar_to_plain(own.pmx, k)
    c_k, _r = own.pmx.encrypt(kp, u_bits)
    return {
        "k": k, "gamma": gamma, "Gamma": Gam, "Gamma_comp": Gam_comp,
        "commit": commit, "kp": kp, "c_k": c_k,
        "ck": _wire(c_k, own.pmx.prof_n2),
    }


@jax.jit
def gg18_r1_prove(mta: MtaBatch, kp, c_k, u_bits, raw):
    """Round 1, per peer: Alice's range proof of c in the peer's ring."""
    A, Bo = mta.alice, mta.bob
    Ra = mta.alice_randoms_from(raw)
    T = mta.alice_init(kp, Ra)
    e = mta.e_limbs(mta.alice_challenge(c_k, T))
    P = mta.alice_finish(e, kp, Ra, u_bits)
    nt = Bo.ctx_nt.prof
    return {
        "z": _wire(T["z"], nt), "u": _wire(T["u"], A.pmx.prof_n2),
        "w": _wire(T["w"], nt), "s": _wire(P["s"], A.pmx.prof_n),
        "s1": _wire(P["s1"], mta.p_s1), "s2": _wire(P["s2"], mta.p_s2),
    }


@jax.jit
def gg18_r2_verify(mta: MtaBatch, ok, ck, pf, rho_bits):
    """Round 2, per peer (Alice = the peer): its ciphertext and range
    proof parsed and checked → (c_a, lanes still sound, the combined
    ciphertext equation's three values, the parsed proof for the strict
    fallback)."""
    A, Bo = mta.alice, mta.bob
    nt = Bo.ctx_nt.prof
    c_a = _unwire(ck, A.pmx.prof_n2)
    T = {"z": _unwire(pf["z"], nt), "u": _unwire(pf["u"], A.pmx.prof_n2),
         "w": _unwire(pf["w"], nt)}
    P = {"s": _unwire(pf["s"], A.pmx.prof_n),
         "s1": _unwire(pf["s1"], mta.p_s1),
         "s2": _unwire(pf["s2"], mta.p_s2)}
    e = mta.e_limbs(mta.alice_challenge(c_a, T))
    ok_i, agg = mta.bob_check_alice_dev(c_a, T, P, e, rho_bits)
    return c_a, ok & ok_i, agg, (T, P, e)


def _halves(tree, B: int):
    """A 2·B-lane tree of arrays → (the γ leg's lanes, the w leg's)."""
    return (jax.tree.map(lambda x: x[:B], tree),
            jax.tree.map(lambda x: x[B:], tree))


def _twice(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.concatenate([x, x], axis=0)


@jax.jit
def gg18_r2_respond(mta: MtaBatch, c_a, gamma, w, raw, X_comp):
    """Round 2, per peer: Bob's homomorphic responses to the peer's
    ciphertext for BOTH of his secrets, γ and w, with their proofs, as one
    2·B-lane batch (lanes [0, B) the γ leg, [B, 2B) the w leg: the same
    moduli, so one pass of every ladder serves both; ``raw`` is drawn for
    2·B lanes). The legs differ in their challenge alone: the w leg's
    binds U = α·G and the public W (MtA with check). → (wire blocks of
    2·B rows, the w leg's U block, Bob's additive shares −β′ mod q of the
    two legs)."""
    A = mta.alice
    B = c_a.shape[0]
    c2 = _twice(c_a)
    Rb = mta.bob_randoms_from(raw)
    b_e = _scalar_to_prof(jnp.concatenate([gamma, w], axis=0), mta.p_e)
    Tb = mta.bob_respond(c2, b_e, Rb)
    T_g, T_w = _halves(Tb, B)
    alpha_q = _mod_q_from_limbs(Rb["alpha"][B:], mta.p_alpha)
    _U, U_comp = _base_mul_compressed(alpha_q)
    e_b = mta.e_limbs(jnp.concatenate([
        mta.bob_challenge(c_a, T_g),
        mta.bob_challenge(c_a, T_w, (U_comp, X_comp)),
    ], axis=0))
    Pb = mta.bob_finish(e_b, b_e, Rb)
    beta = sp.scalar_ring().negmod(
        _mod_q_from_limbs(Rb["beta_prime"], mta.p_bp)
    )
    nt, n2 = A.ctx_nt.prof, A.pmx.prof_n2
    blocks = {
        "cb": _wire(Tb["c_b"], n2), "z": _wire(Tb["z"], nt),
        "zp": _wire(Tb["z_p"], nt), "t": _wire(Tb["t"], nt),
        "v": _wire(Tb["v"], n2), "w": _wire(Tb["w"], nt),
        "s": _wire(Pb["s"], A.pmx.prof_n), "s1": _wire(Pb["s1"], mta.p_s1),
        "s2": _wire(Pb["s2"], mta.p_s2), "t1": _wire(Pb["t1"], mta.p_t1),
        "t2": _wire(Pb["t2"], mta.p_s2),
    }
    return blocks, U_comp, (beta[:B], beta[B:])


@jax.jit
def gg18_r3_verify(mta: MtaBatch, ok, c_k, rs, U_comp, rho_bits, W_peer,
                   X_comp):
    """Round 3, per peer (Alice = self): the peer's two responses (``rs``:
    blocks of 2·B rows, γ leg then w leg) and their proofs parsed and
    checked as one 2·B-lane batch, the curve binding of the w leg, and
    Alice's shares Dec(c_b) mod q of both → (lanes still sound, the
    combined equation's values, the (γ, w) shares, the parsed proofs for
    the strict fallback)."""
    A = mta.alice
    B = c_k.shape[0]
    c2 = _twice(c_k)
    nt, n2 = A.ctx_nt.prof, A.pmx.prof_n2
    Tb = {"c_b": _unwire(rs["cb"], n2), "z": _unwire(rs["z"], nt),
          "z_p": _unwire(rs["zp"], nt), "t": _unwire(rs["t"], nt),
          "v": _unwire(rs["v"], n2), "w": _unwire(rs["w"], nt)}
    Pb = {"s": _unwire(rs["s"], A.pmx.prof_n),
          "s1": _unwire(rs["s1"], mta.p_s1),
          "s2": _unwire(rs["s2"], mta.p_s2),
          "t1": _unwire(rs["t1"], mta.p_t1),
          "t2": _unwire(rs["t2"], mta.p_s2)}
    T_g, T_w = _halves(Tb, B)
    U_pt, ok_u = sp.decompress(U_comp)
    e_b = mta.e_limbs(jnp.concatenate([
        mta.bob_challenge(c_k, T_g),
        mta.bob_challenge(c_k, T_w, (U_comp, X_comp)),
    ], axis=0))
    ok2, agg = mta.alice_check_bob_dev(c2, Tb, Pb, e_b, rho_bits)
    ok = ok & ok_u & ok2[:B] & ok2[B:] & _withcheck_curve(
        _mod_q_from_limbs(Pb["s1"][B:], mta.p_s1),
        _mod_q_from_limbs(e_b[B:], mta.p_e), U_pt, W_peer,
    )
    alpha = mta.alice_decrypt_share(Tb["c_b"])
    return ok, agg, (alpha[:B], alpha[B:]), (c2, Tb, Pb, e_b)


@jax.jit
def gg18_r3_delta(k, gamma, w, alphas, betas):
    """δ_i = k·γ + Σ(α+β) and σ_i = k·w + Σ(α+β) over the peers' legs
    (``alphas`` / ``betas``: per peer a (γ leg, w leg) pair)."""
    ring = sp.scalar_ring()
    d = ring.mulmod(k, gamma)
    s_ = ring.mulmod(k, w)
    for (a_g, a_w), (b_g, b_w) in zip(alphas, betas):
        d = ring.addmod(d, ring.addmod(a_g, b_g))
        s_ = ring.addmod(s_, ring.addmod(a_w, b_w))
    return d, s_, sp.pack_be_32(d)


@jax.jit
def gg18_r4_pok(kpok_raw, gamma, Gamma_comp, bind):
    """Round 4: Schnorr PoK of γ → (A block, s block)."""
    A_comp, s_pok = _blk_schnorr_prove(
        _scalar_from_wide_bytes(kpok_raw), gamma, Gamma_comp, bind
    )
    return A_comp, sp.pack_be_32(s_pok)


@jax.jit
def gg18_r5a_verify(ok, delta_own, Gamma_own, peers):
    """Phase 5A, the peers' side: their Γ decommitments and Schnorr PoKs
    checked as one (q-1)·B-lane batch → (lanes still sound, Σδ, ΣΓ).
    ``peers``: G, blind, gc, A, spok, d, bind, each (q-1, B, ·)."""
    n = peers["G"].shape[0]
    ring = sp.scalar_ring()
    G_comp = _flat(peers["G"])
    bind_p = _flat(peers["bind"])
    G_pt, ok_g = sp.decompress(G_comp)
    ok_p = ok_g & _blk_gamma_check(
        _flat(peers["blind"]), G_comp, bind_p, _flat(peers["gc"])
    )
    ok_p = ok_p & _blk_schnorr_verify(
        _flat(peers["A"]), _scalar_be(_flat(peers["spok"])), G_pt, G_comp,
        bind_p,
    )
    delta = delta_own
    d_peers = _scalar_be(peers["d"])
    for i in range(n):
        delta = ring.addmod(delta, d_peers[i])
    return (ok & jnp.all(ok_p.reshape(n, -1), axis=0), delta,
            _sum_pts(Gamma_own, G_pt, n))


@jax.jit
def gg18_r5a_commit(ok, delta, Gamma_sum, m, k, sigma, raw, va_blind, bind):
    """Phase 5A, the own side: R = δ⁻¹·ΣΓ, then s_i, V_i, A_i and their
    commitment (``raw``: the wide bytes of l_i, ρ_i and the PoK nonces)."""
    ok_R, R_pt, r, rec = _blk_R(delta, Gamma_sum)
    li, rho, ka, kb = (_scalar_from_wide_bytes(raw[x])
                       for x in ("li", "rho", "ka", "kb"))
    s_i, V_i, A_i, vc, ac, cmt = _blk_va(
        m, r, k, sigma, li, rho, R_pt, va_blind, bind
    )
    return {
        "ok": ok & ok_R, "R": R_pt, "r": r, "rec": rec, "li": li,
        "rho": rho, "ka": ka, "kb": kb, "s": s_i, "V": V_i, "A": A_i,
        "vc": vc, "ac": ac, "commit": cmt,
    }


@jax.jit
def gg18_r5b(ka, kb, s_i, l_i, R_pt, vc, ac, bind):
    """Phase 5B: PedersenPoK of (s_i, l_i) → (A block, sa, sb blocks)."""
    Apok, sa, sb = _blk_pedersen_prove(ka, kb, s_i, l_i, R_pt, vc, ac, bind)
    return Apok, sp.pack_be_32(sa), sp.pack_be_32(sb)


def _decompress_pair(a_comp, b_comp):
    """Two blocks of points decompressed as one batch (one square-root
    ladder in the program, not two)."""
    n = a_comp.shape[0]
    pts, ok = sp.decompress(jnp.concatenate([a_comp, b_comp], axis=0))
    first = type(pts)(*(leaf[:n] for leaf in pts))
    second = type(pts)(*(leaf[n:] for leaf in pts))
    return first, second, ok[:n] & ok[n:]


@jax.jit
def gg18_r5c_verify(ok, V_own, A_own, R_pt, peers):
    """Phase 5C, the peers' side: their (V, A) decommitments and
    PedersenPoKs checked as one batch → (lanes still sound, ΣV, ΣA).
    ``peers``: vc, ac, blind, c, apok, sa, sb, bind, each (q-1, B, ·)."""
    n = peers["vc"].shape[0]
    vc, ac = _flat(peers["vc"]), _flat(peers["ac"])
    bind_p = _flat(peers["bind"])
    V_pt, A_pt, ok_p = _decompress_pair(vc, ac)
    ok_p = ok_p & _blk_va_check(
        _flat(peers["blind"]), vc, ac, bind_p, _flat(peers["c"])
    )
    ok_p = ok_p & _blk_pedersen_verify(
        _flat(peers["apok"]), _scalar_be(_flat(peers["sa"])),
        _scalar_be(_flat(peers["sb"])), V_pt, _tile_pt(R_pt, n), vc, ac,
        bind_p,
    )
    V_sum, A_sum = _sum_pts_many([(V_own, V_pt), (A_own, A_pt)], n)
    return ok & jnp.all(ok_p.reshape(n, -1), axis=0), V_sum, A_sum


@jax.jit
def gg18_r5c_commit(V_sum, A_sum, m, r, Y, rho, li, ut_blind, bind):
    """Phase 5C, the own side: V = ΣV − m·G − r·Y, then U_i = ρ_i·V,
    T_i = l_i·ΣA and their commitment."""
    V = _blk_V(V_sum, m, r, Y)
    U_i, T_i, uc, tc, cmt = _blk_ut(rho, li, V, A_sum, ut_blind, bind)
    return {"U": U_i, "T": T_i, "uc": uc, "tc": tc, "commit": cmt}


@jax.jit
def gg18_r5e(ok, U_own, T_own, s_own, peers):
    """Phase 5E: the peers' (U, T) decommitments checked as one batch,
    ΣU == ΣT, and the own partial signature's block.
    ``peers``: uc, tc, blind, c, bind, each (q-1, B, ·)."""
    n = peers["uc"].shape[0]
    uc, tc = _flat(peers["uc"]), _flat(peers["tc"])
    U_pt, T_pt, ok_p = _decompress_pair(uc, tc)
    ok_p = ok_p & _blk_ut_check(
        _flat(peers["blind"]), uc, tc, _flat(peers["bind"]),
        _flat(peers["c"]),
    )
    ok = ok & jnp.all(ok_p.reshape(n, -1), axis=0)
    U_sum, T_sum = _sum_pts_many([(U_own, U_pt), (T_own, T_pt)], n)
    ok = ok & sp.equal(U_sum, T_sum)
    return ok, sp.pack_be_32(s_own)


@jax.jit
def gg18_final(ok, s_own, s_peers, m, r, rec, Y):
    """Combine the partial signatures, normalise to low s and verify each
    signature in-protocol → (r block, s block, recovery ids, ok)."""
    ring = sp.scalar_ring()
    s = s_own
    s_p = _scalar_be(s_peers)
    for i in range(s_peers.shape[0]):
        s = ring.addmod(s, s_p[i])
    ok_f, s, rec = _blk_final(s, m, r, Y, rec)
    return sp.pack_be_32(r), sp.pack_be_32(s), rec, ok & ok_f


# the programs a served GG18 wave runs, by the wire round whose handler
# runs them (a device trace names them ``jit_<name>``)
ROUND_PROGRAMS = {
    0: ("gg18_setup",),
    1: ("gg18_r1_commit", "gg18_r1_prove"),
    2: ("gg18_r2_verify", "gg18_r2_respond"),
    3: ("gg18_r3_verify", "gg18_r3_delta"),
    4: ("gg18_r4_pok",),
    5: ("gg18_r5a_verify", "gg18_r5a_commit"),
    6: ("gg18_r5b",),
    7: ("gg18_r5c_verify", "gg18_r5c_commit"),
    9: ("gg18_r5e", "gg18_final"),
}


# ---------------------------------------------------------------------------
# q-party batched co-signing fabric (bench / loopback deployments)
# ---------------------------------------------------------------------------


class GG18BatchCoSigners:
    """Runs B concurrent (t+1)-of-n GG18 signing sessions with every
    signer's round compute batched on device (the in-process measurement
    fabric — the distributed node runs the same kernels per party).

    ``party_ids``: the signing quorum (any ≥ t+1 subset of the keygen
    universe — reference ecdsa_signing_session.go:96-139).
    ``party_shares[i]`` are signer i's per-wallet shares (same wallet order
    across parties, one quorum topology per batch).
    """

    def __init__(
        self,
        party_ids: Sequence[str],
        party_shares: Sequence[Sequence[KeygenShare]],
        preparams: Optional[Dict[str, PreParams]] = None,
        dom: Domains = Domains(),
        rng=secrets,
        *,
        mta_impl: Optional[str] = None,
    ):
        self.q = len(party_ids)
        assert self.q >= 2, "need at least a 2-party quorum"
        self.ids = list(party_ids)
        self.B = len(party_shares[0])
        self.dom = dom
        self.rng = rng
        self.ring = sp.scalar_ring()

        first = party_shares[0][0]
        assert self.q >= first.threshold + 1, "quorum below threshold+1"
        universe_xs = party_xs(first.participants)
        quorum_xs = [universe_xs[p] for p in party_ids]
        # all ordered MtA directions
        self.pairs = [
            (a, b)
            for a in range(self.q)
            for b in range(self.q)
            if a != b
        ]
        # MtA implementation: "paillier" (default — the GG18 MtA with
        # range proofs), "ot" (OT-based Gilboa multiplication,
        # protocol.ecdsa.mta_ot: no Paillier anywhere in signing;
        # KOS/DKLs-style checks with identifiable abort — see
        # SECURITY.md "OT-MtA" for exact coverage), or
        # "none" (curve state only — no MtA contexts, cannot sign();
        # the multichip dryrun builds its sharding probe this way via
        # :meth:`curve_only` instead of hand-wiring ``__new__``)
        self.mta_impl = os.environ.get("MPCIUM_MTA", "paillier")
        if mta_impl is not None:
            self.mta_impl = mta_impl
        if self.mta_impl not in ("paillier", "ot", "none"):
            raise ValueError(
                f"MPCIUM_MTA={self.mta_impl!r}: expected 'paillier' or 'ot'"
            )
        if self.mta_impl == "ot":
            from ..protocol.ecdsa.mta_ot import OTMtALeg

            self.ctx = None
            self.mta = None
            self.ot_legs = {
                (a, b): OTMtALeg(
                    f"{party_ids[a]}->{party_ids[b]}", rng=rng
                )
                for (a, b) in self.pairs
            }
        elif self.mta_impl == "none":
            self.ctx = None
            self.mta = None
            self.ot_legs = None
        else:
            if preparams is None:
                raise ValueError("mta_impl='paillier' requires preparams")
            self.ctx = [PartyCtx(pid, preparams[pid], rng) for pid in party_ids]
            self.mta = {
                (a, b): MtaBatch(self.ctx[a], self.ctx[b], dom)
                for (a, b) in self.pairs
            }
        # additive shares w_i = λ_i·x_i mod q (λ shared across the batch)
        self.w = []
        self.W_pts = []
        for i, (pid, shares) in enumerate(zip(party_ids, party_shares)):
            lam = hm.lagrange_coeff(quorum_xs, universe_xs[pid], Q)
            w_ints = [lam * s.share % Q for s in shares]
            w_limbs = jnp.asarray(bn.batch_to_limbs(w_ints, P256))
            self.w.append(w_limbs)
            for s in shares:
                if s.key_type != "secp256k1":
                    raise ValueError("wrong key type")
                if s.self_x != universe_xs[pid]:
                    raise ValueError("party_shares misaligned with party_ids")
            W, _ = _base_mul_compressed(w_limbs)
            self.W_pts.append(W)
        # wallet public keys (host decompress once at setup)
        pubs = [hm.secp_decompress(s.public_key) for s in party_shares[0]]
        self.Y = sp.from_host(pubs)

    @classmethod
    def curve_only(
        cls,
        party_ids: Sequence[str],
        party_shares: Sequence[Sequence[KeygenShare]],
        rng=secrets,
    ) -> "GG18BatchCoSigners":
        """Curve state (w, W_pts, Y) without any MtA machinery — for
        sharding probes and dryruns that exercise the batched point math
        but never run the signing protocol. ``sign()`` raises."""
        return cls(party_ids, party_shares, None, rng=rng, mta_impl="none")

    # -- small helpers -------------------------------------------------------

    def _rand_scalar(self) -> jnp.ndarray:
        return _scalar_from_wide_bytes(
            jnp.asarray(rand_bits(self.B, 320, self.rng))
        )

    def _rand_scalars_q(self) -> jnp.ndarray:
        """(q, B, 22) uniform scalars mod q (one upload + one dispatch)."""
        raw = rand_bits(self.q * self.B, 320, self.rng).reshape(
            self.q, self.B, 40
        )
        return _scalar_from_wide_bytes(jnp.asarray(raw))

    def _blinds_q(self) -> jnp.ndarray:
        return jnp.asarray(
            rand_bits(self.q * self.B, 256, self.rng).reshape(
                self.q, self.B, 32
            )
        )

    # -- the protocol --------------------------------------------------------

    def sign(
        self, digests: np.ndarray, phase_times: Optional[dict] = None,
        cohorts: Optional[int] = None,
    ) -> Dict[str, np.ndarray]:
        """``digests``: (B, 32) big-endian digests. Returns dict with
        r, s (B, 32 BE bytes), recovery (B,), ok mask (B,).

        ``phase_times``: optional dict — when given (or when mpctrace is
        armed), the engine blocks at phase boundaries and records wall
        seconds per protocol phase as ``phase:*`` spans plus the legacy
        dict (bench diagnostics; adds sync overhead only then).

        ``cohorts``: counter-phase cohort count for the signing tail
        (engine/pipeline; None → MPCIUM_PIPELINE_COHORTS, default 2).
        Signatures and transcripts are bit-identical for every K —
        randomness is drawn full-batch in serial order before any
        split."""
        if self.mta_impl == "none":
            raise RuntimeError(
                "curve_only signer has no MtA contexts — cannot sign()"
            )
        _pt = tracing.PhaseTimer(
            "gg18.sign", _trace_sync, phase_times=phase_times,
            node="engine", tid=f"gg18:B{self.B}",
        )
        _mark = _pt.mark
        # first call per (engine, shape-bucket) pays the compile wall:
        # ledger it (one set lookup + None on every later call)
        # mpcshape: unbounded-ok — B is pow-2 snapped upstream (scheduler chunks via engine/buckets.floor_bucket; bench via bucket_b)
        _cw = compile_watch.begin(
            "gg18.sign", f"B{self.B}|q{self.q}|mta={self.mta_impl}"
        )
        B, q = self.B, self.q
        ring = self.ring
        m = ring.reduce(
            bn.bytes_to_limbs_le(jnp.asarray(digests[:, ::-1].copy()), P256, 22)
        )

        # ---- round 1: k, γ, Γ commitments; shared c_i = Enc_i(k_i) ---------
        k_st = self._rand_scalars_q()
        gamma_st = self._rand_scalars_q()
        k = [k_st[i] for i in range(q)]
        gamma = [gamma_st[i] for i in range(q)]
        g_blind = self._blinds_q()
        Gamma, Gamma_comp, g_commit = [], [], []
        for i in range(q):
            pt, comp, commit = _blk_gamma(gamma_st[i], g_blind[i], _idx_row(i, B))
            Gamma.append(pt)
            Gamma_comp.append(comp)
            g_commit.append(commit)

        if self.mta_impl == "ot":
            # ---- OT path: no Paillier in signing at all. Rounds 1-3 of
            # the MtA machinery collapse into Gilboa OT multiplication
            # per (ordered pair, secret): alpha+beta ≡ k_a·secret_b
            # (mod q). Commitments/Γ from round 1 above are unchanged,
            # as is everything from δ/σ assembly on — the signature
            # itself is still verified in-protocol at phase 5.
            _mark("r1_commit_encrypt_rangeproof", *Gamma_comp)
            ok = jnp.ones((B,), bool)
            alpha_shares = {}
            beta_shares = {}
            # pipeline chunking knob (MPCIUM_OT_CHUNKS; 0/unset → auto
            # from B) — resolved here so every leg of the quorum runs
            # the same schedule
            from ..protocol.ecdsa.mta_ot import resolve_chunks

            ot_chunks = resolve_chunks(B)
            ot_timings = {} if _pt.on else None
            for (a, b) in self.pairs:
                leg = self.ot_legs[(a, b)]
                # one extension serves BOTH products (same k_a choice
                # bits; set-separated pad domains — mta_ot.run_multi)
                shares = leg.run_multi(
                    k[a], (gamma[b], self.w[b]),
                    chunks=ot_chunks, timings=ot_timings,
                )
                for name, (al, be) in zip(("gamma", "w"), shares):
                    alpha_shares[(a, b, name)] = al
                    beta_shares[(a, b, name)] = be
            # host/device A/B split of the OT phase rides the span as
            # attrs (and the legacy dict as r2_mta_ot_* keys): host_s is
            # worker-thread busy time, device is main-thread block time
            # on device arrays; hidden host time (host_s minus the
            # residual main-thread wait on the worker) over host_s is
            # the pipeline's overlap ratio.
            ot_attrs = {}
            if ot_timings:
                host_s = ot_timings.get("host_s", 0.0)
                hidden = max(0.0, host_s - ot_timings.get("host_wait_s", 0.0))
                ot_attrs = {
                    "host": host_s,
                    "device": ot_timings.get("device_wait_s", 0.0),
                    "overlap_ratio": hidden / host_s if host_s > 0 else 0.0,
                    "chunks": float(ot_chunks),
                }
            _mark("r2_mta_ot",
                  *[alpha_shares[(p[0], p[1], "w")] for p in self.pairs],
                  **ot_attrs)
            # Identifiable abort (ISSUE 16): every leg ran its KOS /
            # Gilboa / consistency checks inside run_multi; a blamed
            # lane aborts the cohort with the offending (lane, party)
            # named, so the scheduler can quarantine exactly those
            # sessions and re-pack the survivors. Alice = the leg's
            # receiver = party a (its choice bits are k_a); Bob = party
            # b. A lane keeps its FIRST blame — a tampered extension
            # garbles downstream pads, so later checks on the same lane
            # are side effects, not independent evidence.
            blamed: Dict[int, Tuple[str, str]] = {}
            for (a, b) in self.pairs:
                per_lane = self.ot_legs[(a, b)].check_blame()
                if per_lane is None:
                    continue
                for lane, verdict in enumerate(per_lane):
                    if verdict is None or lane in blamed:
                        continue
                    role, check = verdict
                    blamed[lane] = (
                        self.ids[a] if role == "alice" else self.ids[b],
                        check,
                    )
            if blamed:
                from .abort import CohortAbort

                raise CohortAbort(
                    [(lane, pid, check)
                     for lane, (pid, check) in sorted(blamed.items())],
                    engine="gg18.sign",
                )
            out = self._finish_sign(
                _pt, m, ok, k, gamma, Gamma, Gamma_comp,
                g_commit, g_blind, alpha_shares, beta_shares,
                cohorts=cohorts,
            )
            compile_watch.finish(_cw)
            return out

        # per-party encryption of k_i (one ciphertext reused by all pairs)
        c_k, u_k, k_plain = [], [], []
        for i in range(q):
            u_bits = rand_bit_tensor(B, RAND_BITS, self.rng)
            kp = _scalar_to_plain(self.ctx[i].pmx, k[i])
            c, _r = self.ctx[i].pmx.encrypt(kp, u_bits)
            c_k.append(c)
            u_k.append(u_bits)
            k_plain.append(kp)

        mta_state: Dict[Tuple[int, int], Dict] = {}
        for (a, b) in self.pairs:
            mta = self.mta[(a, b)]
            Ra = mta.alice_randoms(B, self.rng)
            T = mta.alice_init(k_plain[a], Ra)
            e = mta.e_limbs(mta.alice_challenge(c_k[a], T))
            P = mta.alice_finish(e, k_plain[a], Ra, u_k[a])
            mta_state[(a, b)] = {"Ra": Ra, "T": T, "e": e, "P": P}
        _mark("r1_commit_encrypt_rangeproof",
              *[mta_state[p]["P"]["s"] for p in self.pairs])

        ok = jnp.ones((B,), bool)

        # ---- round 2: Bob verifies + responds (γ and w) --------------------
        for (a, b) in self.pairs:
            mta = self.mta[(a, b)]
            st = mta_state[(a, b)]
            ok = ok & mta.bob_check_alice(
                c_k[a], st["T"], st["P"], st["e"], rng=self.rng
            )
            for name, secret in (("gamma", gamma[b]), ("w", self.w[b])):
                Rb = mta.bob_randoms(B, self.rng)
                b_e = _scalar_to_prof(secret, mta.p_e)
                Tb = mta.bob_respond(c_k[a], b_e, Rb)
                extra = ()
                U_pt = None
                if name == "w":
                    alpha_q = _mod_q_from_limbs(Rb["alpha"], mta.p_alpha)
                    U_pt, U_comp = _base_mul_compressed(alpha_q)
                    X_comp = sp.compress(self.W_pts[b])
                    extra = (U_comp, X_comp)
                e_b = mta.e_limbs(mta.bob_challenge(c_k[a], Tb, extra))
                Pb = mta.bob_finish(e_b, b_e, Rb)
                st[name] = {"Rb": Rb, "Tb": Tb, "e": e_b, "Pb": Pb, "U": U_pt}

        _mark("r2_mta_respond", ok,
              *[mta_state[p]["w"]["Tb"]["c_b"] for p in self.pairs])

        # ---- round 3: Alice verifies + decrypts; δ_i, σ_i ------------------
        alpha_shares = {}   # (a, b, name) -> alice's additive share mod q
        beta_shares = {}    # (a, b, name) -> bob's additive share mod q
        for (a, b) in self.pairs:
            mta = self.mta[(a, b)]
            st = mta_state[(a, b)]
            for name in ("gamma", "w"):
                sub = st[name]
                ok = ok & mta.alice_check_bob(
                    c_k[a], sub["Tb"], sub["Pb"], sub["e"], rng=self.rng
                )
                if name == "w":
                    # with-check: s1·G ?= U + e·W_b (one fused dispatch)
                    ok = ok & _withcheck_curve(
                        _mod_q_from_limbs(sub["Pb"]["s1"], mta.p_s1),
                        _mod_q_from_limbs(sub["e"], mta.p_e),
                        sub["U"],
                        self.W_pts[b],
                    )
                alpha_shares[(a, b, name)] = mta.alice_decrypt_share(
                    sub["Tb"]["c_b"]
                )
                beta_shares[(a, b, name)] = ring.negmod(
                    _mod_q_from_limbs(sub["Rb"]["beta_prime"], mta.p_bp)
                )

        out = self._finish_sign(
            _pt, m, ok, k, gamma, Gamma, Gamma_comp, g_commit, g_blind,
            alpha_shares, beta_shares, cohorts=cohorts,
        )
        compile_watch.finish(_cw)
        return out

    def _finish_sign(
        self, _pt, m, ok, k, gamma, Gamma, Gamma_comp, g_commit,
        g_blind, alpha_shares, beta_shares,
        cohorts: Optional[int] = None,
    ) -> Dict[str, np.ndarray]:
        """Shared tail of both MtA implementations, cohort-pipelined
        (engine/pipeline): δ/σ assembly, R reconstruction, Schnorr PoKs,
        the full phase-5 commit–reveal and the in-protocol ECDSA
        verification. With K>1 each cohort's device rounds dispatch
        while another cohort's signature egress drains on the pipeline
        host worker; K=1 is byte-for-byte the old serial path.

        Transcript discipline: ALL tail randomness is drawn here — full
        batch, in the K=1 serial order (kpok, li, ri, ka, kb, va_blind,
        ut_blind) — then row-sliced per cohort, so the rng stream and
        every commitment/signature byte is identical for every K. (The
        MtA rounds BEFORE this tail always run full-batch: the OT
        extension's PRF tags are width- and counter-dependent, so
        splitting them would change transcripts; its own chunk overlap
        already pipelines that stage.)"""
        B, q = self.B, self.q
        rand = {
            "kpok": self._rand_scalars_q(),
            "li": self._rand_scalars_q(),
            "ri": self._rand_scalars_q(),
            "ka": self._rand_scalars_q(),
            "kb": self._rand_scalars_q(),
            "va_blind": self._blinds_q(),
            "ut_blind": self._blinds_q(),
        }
        from . import pipeline as pl

        plan = pl.CohortPlan.for_batch(B, cohorts)
        if plan.serial:
            r_d, s_d, rec_d, ok_d = self._tail_cohort(
                _pt.mark, m, ok, k, gamma, Gamma, Gamma_comp, g_commit,
                g_blind, alpha_shares, beta_shares, rand,
                list(self.w), self.Y,
            )
            return _sig_egress(r_d, s_d, rec_d, ok_d)

        # per-cohort phase timers: independent spans (tid …:cN) so the
        # idle meter sees the counter-phase overlap; legacy phase dicts
        # are summed back into the caller's afterwards
        cohort_phases = [
            {} if _pt.phases is not None else None for _ in range(plan.k)
        ]

        def job(ci: int, sl: slice):
            def run():
                pt_c = tracing.PhaseTimer(
                    "gg18.sign", _trace_sync,
                    phase_times=cohort_phases[ci],
                    node="engine", tid=f"gg18:B{B}:c{ci}",
                )
                r_d, s_d, rec_d, ok_d = self._tail_cohort(
                    pt_c.mark,
                    m[sl], ok[sl],
                    [x[sl] for x in k],
                    [x[sl] for x in gamma],
                    [_slice_pt(p, sl) for p in Gamma],
                    [x[sl] for x in Gamma_comp],
                    [x[sl] for x in g_commit],
                    g_blind[:, sl],
                    {kk: v[sl] for kk, v in alpha_shares.items()},
                    {kk: v[sl] for kk, v in beta_shares.items()},
                    {kk: v[:, sl] for kk, v in rand.items()},
                    [x[sl] for x in self.w],
                    _slice_pt(self.Y, sl),
                )
                res = yield (
                    "sig_egress",
                    lambda: _sig_egress(r_d, s_d, rec_d, ok_d),
                )
                return res

            return run

        parts = pl.run_counter_phase(
            [job(ci, sl) for ci, sl in enumerate(plan.slices())]
        )
        if _pt.phases is not None:
            for d in cohort_phases:
                for name, v in (d or {}).items():
                    _pt.phases[name] = _pt.phases.get(name, 0.0) + v
        return {
            key: pl.merge_rows([p[key] for p in parts])
            for key in parts[0]
        }

    def _tail_cohort(
        self, _mark, m, ok, k, gamma, Gamma, Gamma_comp, g_commit,
        g_blind, alpha_shares, beta_shares, rand, w, Y,
    ):
        """One cohort's tail rounds over pre-sliced device views —
        every kernel here is per-lane in B, so a cohort slice computes
        exactly the rows it would as part of the full batch. Returns
        DEVICE tensors (r, s, recovery, ok); the host egress is the
        caller's pipeline stage."""
        B = int(m.shape[0])
        q = self.q
        ring = self.ring
        delta_i, sigma_i = [], []
        for i in range(q):
            d = ring.mulmod(k[i], gamma[i])
            s_ = ring.mulmod(k[i], w[i])
            for j in range(q):
                if j == i:
                    continue
                d = ring.addmod(
                    d,
                    ring.addmod(
                        alpha_shares[(i, j, "gamma")],
                        beta_shares[(j, i, "gamma")],
                    ),
                )
                s_ = ring.addmod(
                    s_,
                    ring.addmod(
                        alpha_shares[(i, j, "w")], beta_shares[(j, i, "w")]
                    ),
                )
            delta_i.append(d)
            sigma_i.append(s_)

        _mark("r3_verify_decrypt", ok, *delta_i, *sigma_i)

        # ---- rounds 4-9: R reconstruction + phase 5 (jitted per-party
        # blocks, each compiled once and reused q times) ------------------
        for i in range(q):
            ok = ok & _blk_gamma_check(
                g_blind[i], Gamma_comp[i], _idx_row(i, B), g_commit[i]
            )
        delta = delta_i[0]
        Gamma_sum = Gamma[0]
        for i in range(1, q):
            delta = ring.addmod(delta, delta_i[i])
            Gamma_sum = _blk_point_add(Gamma_sum, Gamma[i])
        ok_R, R_pt, r, rec = _blk_R(delta, Gamma_sum)
        ok = ok & ok_R
        kpok = rand["kpok"]
        for i in range(q):
            ok = ok & _blk_schnorr(
                kpok[i], gamma[i], Gamma[i], Gamma_comp[i], _idx_row(i, B)
            )
        _mark("r4_R_reconstruct_pok", ok, r)

        # phase 5A: commitments to V_i, A_i (randomness pre-drawn by
        # _finish_sign in serial order — see its transcript note)
        li = rand["li"]
        ri = rand["ri"]
        ka = rand["ka"]
        kb = rand["kb"]
        va_blind = rand["va_blind"]
        ut_blind = rand["ut_blind"]
        s_i, V_i, A_i, V_c, A_c, va_commit = [], [], [], [], [], []
        for i in range(q):
            si, Vi, Ai, vc, ac, cmt = _blk_va(
                m, r, k[i], sigma_i[i], li[i], ri[i], R_pt, va_blind[i],
                _idx_row(i, B),
            )
            s_i.append(si); V_i.append(Vi); A_i.append(Ai)
            V_c.append(vc); A_c.append(ac); va_commit.append(cmt)
        # phase 5B: decommit + PedersenPoK
        for i in range(q):
            ok = ok & _blk_pedersen(
                ka[i], kb[i], s_i[i], li[i], V_i[i], R_pt, V_c[i], A_c[i],
                va_blind[i], _idx_row(i, B), va_commit[i],
            )
        # phase 5C/5D: U/T commit–reveal + ΣU == ΣT
        V_sum, A_sum = V_i[0], A_i[0]
        for i in range(1, q):
            V_sum = _blk_point_add(V_sum, V_i[i])
            A_sum = _blk_point_add(A_sum, A_i[i])
        V = _blk_V(V_sum, m, r, Y)
        U_pts, T_pts, U_c, T_c, ut_commit = [], [], [], [], []
        for i in range(q):
            Ui, Ti, uc, tc, cmt = _blk_ut(
                ri[i], li[i], V, A_sum, ut_blind[i], _idx_row(i, B)
            )
            U_pts.append(Ui); T_pts.append(Ti)
            U_c.append(uc); T_c.append(tc); ut_commit.append(cmt)
        for i in range(q):
            ok = ok & _blk_ut_check(
                ut_blind[i], U_c[i], T_c[i], _idx_row(i, B), ut_commit[i]
            )
        U_s, T_s = U_pts[0], T_pts[0]
        for i in range(1, q):
            U_s = _blk_point_add(U_s, U_pts[i])
            T_s = _blk_point_add(T_s, T_pts[i])
        ok = ok & _blk_point_eq(U_s, T_s)
        # phase 5E: reveal + combine + verify — the carried round state
        # goes through the donated final step (rebind-only: MPS906)
        s = s_i[0]
        for i in range(1, q):
            s = ring.addmod(s, s_i[i])
        st = {"s": s, "m": m, "r": r, "rec": rec, "ok": ok}
        st = _step_final(st, Y)
        _mark("r5_phase5_combine_verify", st["ok"], st["s"])
        return st["r"], st["s"], st["rec"], st["ok"]


def _slice_pt(pt, sl: slice):
    """Row-slice a point pytree (NamedTuple of (B, …) leaf arrays) into
    one cohort's lane view."""
    return type(pt)(*(leaf[sl] for leaf in pt))


@functools.partial(jax.jit, donate_argnums=(0,))
def _step_final(st, Y):
    """Phase-5E combine + in-protocol verify as a DONATED round step:
    the carried per-round state pytree {s, m, r, rec, ok} is consumed
    (XLA reuses/frees its buffers — the HBM headroom for B=16384) and
    replaced by the output state. Callers rebind, never re-read
    (mpcshape MPS906)."""
    ok_f, s, rec = _blk_final(st["s"], st["m"], st["r"], Y, st["rec"])
    return {"r": st["r"], "s": s, "rec": rec, "ok": st["ok"] & ok_f}


def _sig_egress(r, s, rec, ok) -> Dict[str, np.ndarray]:
    """Signature egress: device limbs → host BE bytes. Runs as a
    pipeline host stage under K>1."""
    return {
        "r": np.asarray(bn.limbs_to_bytes_le(r, P256, 32))[:, ::-1].copy(),  # mpcflow: host-ok — signature egress
        "s": np.asarray(bn.limbs_to_bytes_le(s, P256, 32))[:, ::-1].copy(),  # mpcflow: host-ok — signature egress
        "recovery": np.asarray(rec),  # mpcflow: host-ok — signature egress
        "ok": np.asarray(ok),  # mpcflow: host-ok — per-wallet verdicts, egress with the signatures
    }


def dealer_keygen_secp_batch(
    n_wallets: int,
    party_ids: Sequence[str],
    threshold: int,
    rng=secrets,
    preparams: Optional[Dict[str, PreParams]] = None,
) -> List[List[KeygenShare]]:
    """Trusted-dealer batch keygen for tests/bench setup ONLY — production
    wallets come from protocol.ecdsa.keygen. result[i] belongs to
    party_ids[i], wallet order aligned.

    With ``preparams``, shares also carry the keygen aux material
    (paillier/ring-Pedersen maps + VSS commitments) that the distributed
    signing parties (per-session and batched) consume."""
    xs = party_xs(party_ids)
    out: List[List[KeygenShare]] = [[] for _ in party_ids]
    aux_by_pid: Dict[str, Dict] = {}
    if preparams is not None:
        for pid in party_ids:
            pre = preparams[pid]
            aux_by_pid[pid] = {
                "paillier_sk": pre.paillier.to_json(),
                "preparams": {
                    "ntilde": str(pre.NTilde),
                    "h1": str(pre.h1),
                    "h2": str(pre.h2),
                },
                "peer_paillier": {
                    p: str(preparams[p].paillier.N)
                    for p in party_ids
                    if p != pid
                },
                "peer_ring_pedersen": {
                    p: {
                        "ntilde": str(preparams[p].NTilde),
                        "h1": str(preparams[p].h1),
                        "h2": str(preparams[p].h2),
                    }
                    for p in party_ids
                    if p != pid
                },
            }
    for _ in range(n_wallets):
        secret = rng.randbelow(Q - 1) + 1
        coeffs, shares = hm.shamir_share(
            secret, threshold, [xs[p] for p in party_ids], Q, rng=rng
        )
        pub = hm.secp_compress(hm.secp_mul(secret, hm.SECP_G))
        vss = (
            [hm.secp_compress(hm.secp_mul(c, hm.SECP_G)) for c in coeffs]
            if preparams is not None
            else []
        )
        for i, pid in enumerate(party_ids):
            out[i].append(
                KeygenShare(
                    key_type="secp256k1",
                    share=shares[xs[pid]],
                    self_x=xs[pid],
                    public_key=pub,
                    vss_commitments=list(vss),
                    participants=sorted(party_ids),
                    threshold=threshold,
                    aux=aux_by_pid.get(pid, {}),
                )
            )
    return out


for _cls in (PartyCtx, MtaBatch):
    jax.tree_util.register_pytree_node(
        _cls, _cls._tree_flatten, _cls._tree_unflatten
    )
