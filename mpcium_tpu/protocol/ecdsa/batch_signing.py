"""Distributed batched GG18 threshold-ECDSA signing: ONE protocol instance
signs B wallets' digests concurrently.

This is the secp256k1 face of the TPU batch engine (SURVEY.md §7.2 step 5)
— the distributed counterpart of the in-process measurement fabric
:class:`engine.gg18_batch.GG18BatchCoSigners`, and the batch analogue of
the per-session :class:`.signing.ECDSASigningParty` (reference
ecdsa_signing_session.go drives one tss-lib party per tx). Each quorum
member exchanges fixed-shape BYTE BLOCKS (B-row limb serializations) and
computes every round with the engine's jitted device kernels; the
scheduler (consumers.batch_scheduler) buckets concurrent requests into
these batches.

Wire schedule (9 network rounds — the same round structure as GG18,
reference ecdsa_rounds.go:16-25):

  R1  broadcast  Γ-commitment block + Enc_i(k_i) ciphertext block
      unicast→j  MtA range proof of Enc_i(k_i) in j's ring
  R2  unicast→j  MtA responses (γ and w legs): c_b + range proofs
  R3  broadcast  δ_i block (after verifying responses + CRT decrypting)
  R4  broadcast  Γ_i decommit + Schnorr PoK of γ_i
  R5  broadcast  phase-5A (V_i, A_i) commitment block
  R6  broadcast  5B decommit + Pedersen PoK of (s_i, l_i)
  R7  broadcast  5C (U_i, T_i) commitment block
  R8  broadcast  5D decommit
  R9  broadcast  partial-signature block s_i
  finalize       combine, low-s normalize, batched ECDSA verify → ok mask

Per-lane semantics: proof/commitment failures mark only their wallet's
lane false (the result carries a per-session ok mask); structural
violations (bad block sizes, equivocation) abort the batch with the
culprit attributed, like the per-session protocol.

All wallets in a batch must share (participants, threshold, epoch) AND
the quorum's Paillier/ring-Pedersen material (see
:func:`quorum_material_digest` — the scheduler buckets on it): the engine
builds one modulus context per party.

Device work: every handler runs the engine's jitted ROUND PROGRAMS
(``gb.gg18_*``) and nothing else on the device. Wire blocks are parsed
into numpy byte arrays on the host and handed to a program whole; a
program returns the next blocks as byte arrays. No ``jnp`` operation
runs outside a program, so after a batch of a shape has run once a
served batch asks XLA for nothing. The modulus contexts of a committee
(Toeplitz constants, comb tables: ~0.6 GB on the device a node, the
tables made there by ops/modmul's ``_k_comb_rows``) are kept across
batches in the :class:`ContextCache` the node's scheduler owns.
"""
from __future__ import annotations

import hashlib
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from ... import wire
from ...core import bignum as bn
from ...core import hostmath as hm
from ...core import secp256k1_jax as sp
from ...core.paillier import PaillierPrivateKey, PreParams
from ...engine import gg18_batch as gb
from ...ops.paillier_mxu import RAND_BITS
from ...perf import compile_watch
from ...utils import log, tracing
from ..base import (BatchBlockMixin, KeygenShare, PartyBase, ProtocolError,
                    RoundMsg, party_xs)

Q = hm.SECP_N

R1B = "gg18/b/1/commit"
R1A = "gg18/b/1/rangeproof"
R2 = "gg18/b/2/respond"
R3 = "gg18/b/3/delta"
R4 = "gg18/b/4/decommit"
R5 = "gg18/b/5/va-commit"
R6 = "gg18/b/6/va-reveal"
R7 = "gg18/b/7/ut-commit"
R8 = "gg18/b/8/ut-reveal"
R9 = "gg18/b/9/partial"

# The device phases of one signer's batch, in protocol order: handler ->
# the ``phase:gg18_<name>`` span around its device work (utils/tracing;
# child of the ``round:`` span of the message that completed the round).
# The benchmark's readers and OBSERVABILITY.md take the names from here.
PHASES = {
    "start": "r1_commit_prove",
    "_respond": "r2_mta_respond",
    "_delta": "r3_verify_decrypt",
    "_decommit_gamma": "r4_pok",
    "_phase5a": "r5a_R_va_commit",
    "_phase5b": "r5b_pedersen_pok",
    "_phase5c": "r5c_verify_ut_commit",
    "_phase5d": "r5d_reveal",
    "_partial": "r5e_check_partial",
    "_finalize": "combine_verify",
}
PHASE_SPANS = tuple(f"phase:gg18_{name}" for name in PHASES.values())


def quorum_material_digest(share: KeygenShare) -> str:
    """Digest of the committee's shared Paillier/ring-Pedersen material.
    Equal across the quorum's nodes for wallets created by the same
    committee generation — the scheduler's batch-homogeneity key (one
    modulus context set per batch)."""
    aux = share.aux
    if not aux or "paillier_sk" not in aux:
        return ""
    sk = aux["paillier_sk"]
    own_n = int(sk["p"]) * int(sk["q"])
    mat = {
        "paillier": dict(aux.get("peer_paillier", {})),
        "ring": {
            pid: dict(rp)
            for pid, rp in aux.get("peer_ring_pedersen", {}).items()
        },
    }
    mat["paillier"][share_owner_key(share)] = str(own_n)
    mat["ring"][share_owner_key(share)] = dict(aux["preparams"])
    return hashlib.sha256(wire.canonical_json(mat)).hexdigest()


def share_owner_key(share: KeygenShare) -> str:
    """The owning party's ID, recovered from self_x within the sorted
    participant universe."""
    xs = party_xs(share.participants)
    for pid, x in xs.items():
        if x == share.self_x:
            return pid
    raise ProtocolError("share self_x not in participant universe")


def _hex(arr) -> str:
    """A device or host byte block as the wire's hex string."""
    return np.asarray(arr).tobytes().hex()  # mpcflow: host-ok — wire serialization


class _Rows:
    """Rows [lo, hi) of a device block, cut on the host when the block is
    read for the wire (no device operation)."""

    def __init__(self, block, lo: int, hi: int):
        self.block, self.lo, self.hi = block, lo, hi

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.block)[self.lo:self.hi]  # mpcflow: host-ok — wire serialization


def _both(mask, B: int):
    """A 2·B-lane verdict (γ leg, then w leg) → the B sessions'."""
    mask = np.asarray(mask)  # mpcflow: host-ok — strict-fallback verdicts gate the lanes on host
    return mask[:B] & mask[B:]


def _span_sync(tensors) -> None:
    """Materialize a phase's device results before its span closes so the
    interval is honest device time — only when tracing is armed (untraced
    runs never sync here; engine PhaseTimer discipline)."""
    if tracing.enabled():
        jax.block_until_ready(tensors)  # mpcflow: host-ok — trace instrumentation, only when tracing is armed


class ContextCache:
    """The modulus contexts of the committees ONE node signs for, kept
    across its batches: a context's constants take a second of host work,
    its comb tables ~180 thousand modular products on the device and ~240
    MB of its memory, and every batch of a committee uses the same ones.
    The node's batch scheduler owns one and hands it to each party it
    builds; a party handed none builds its contexts for its one batch, as
    before.

    What stays resident, and for how long (SECURITY.md, "Key material at
    rest and in memory"): the node's own private context holds the digits
    of p−1 and q−1, h_p, h_q and p⁻¹ mod q as device arrays, and the
    randomizer base y drawn when the context was built (fixed "at key
    load": ops/paillier_mxu). An entry is keyed by the party it is of, a
    digest of the committee's Paillier and ring-Pedersen material, the key
    epoch, and whether it holds the private key, so a reshare (a new epoch)
    or a re-keyed committee never meets an old entry. Entries leave when
    least recently used past ``CAP``, when older than ``MAX_AGE_S``, and
    all of them on ``clear`` (the scheduler's ``close``). A context is
    complete when it is published (its named combs at the committee's
    widths are built inside ``build``, on the device, and waited for) and
    is not written afterwards.

    ``metrics``: the node's registry (the scheduler hands its own):
    counters ``party.ecdsa.context_hits_total`` and
    ``party.ecdsa.context_misses_total``, histogram
    ``party.ecdsa.context_build_s`` (one observation a context built, the
    whole of ``build``); none, nothing is counted."""

    CAP = 16          # contexts; a 2-of-3 committee takes three a node
    MAX_AGE_S = 3600  # a context older than this is built anew

    def __init__(self, clock=time.monotonic, metrics=None) -> None:
        self._lock = threading.Lock()
        self._clock = clock
        self._have: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._m_hits = self._m_misses = self._m_build = None
        if metrics is not None:
            self._m_hits = metrics.counter("party.ecdsa.context_hits_total")
            self._m_misses = metrics.counter(
                "party.ecdsa.context_misses_total")
            self._m_build = metrics.histogram("party.ecdsa.context_build_s")

    def get(self, key: tuple, build) -> gb.PartyCtx:
        now = self._clock()
        with self._lock:
            held = self._have.get(key)
            if held is not None and now - held[1] <= self.MAX_AGE_S:
                self._have.move_to_end(key)
                if self._m_hits is not None:
                    self._m_hits.inc()
                return held[0]
        # outside the lock: a second of host work, then the device's
        t0 = time.perf_counter()
        ctx = jax.block_until_ready(build())  # mpcflow: host-ok — a context is published whole; no value is read
        if self._m_build is not None:
            self._m_misses.inc()
            self._m_build.observe(time.perf_counter() - t0)
        with self._lock:
            held = self._have.get(key)
            if held is None or now - held[1] > self.MAX_AGE_S:
                held = self._have[key] = (ctx, now)
            self._have.move_to_end(key)
            while len(self._have) > self.CAP:
                self._have.popitem(last=False)
        return held[0]

    def clear(self) -> None:
        with self._lock:
            self._have.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._have)


class BatchedECDSASigningParty(BatchBlockMixin, PartyBase):
    """One signer's side of a B-session GG18 batch.

    ``shares``: this node's per-wallet key shares (manifest order —
    identical on every quorum member). ``digests``: the B 32-byte
    transaction digests. All shares must come from one committee
    generation (same participants/threshold/epoch/aux material).
    ``cohorts`` is accepted and not used (the scheduler gives both key
    types' parties one, and the benchmark's older scheme file under
    ``tests/benchmark/`` passes it): a GG18 batch runs whole (see
    ``_finalize``). ``metrics``: the node's registry (histogram
    ``party.ecdsa.phase_s``, counter ``party.ecdsa.mta_responses_total``);
    none, none kept. ``contexts``: the node's :class:`ContextCache`; none,
    the contexts are built for this batch and go with it."""

    def __init__(
        self,
        session_id: str,
        self_id: str,
        party_ids: Sequence[str],
        shares: Sequence[KeygenShare],
        digests: Sequence[bytes],
        dom: gb.Domains = gb.Domains(),
        rng=None,
        cohorts: Optional[int] = None,
        metrics=None,
        contexts: Optional[ContextCache] = None,
    ):
        import secrets as _secrets

        super().__init__(session_id, self_id, party_ids, rng or _secrets)
        if len(shares) != len(digests) or not shares:
            raise ValueError("one share per digest required")
        self.B = len(shares)
        self.dom = dom
        first = shares[0]
        digest0 = quorum_material_digest(first)
        if not digest0:
            raise ProtocolError("shares carry no GG18 aux material")
        universe = list(first.participants)
        u_xs = party_xs(universe)
        for s in shares:
            if s.key_type != "secp256k1":
                raise ProtocolError("wrong key type for GG18 batch signing")
            if s.participants != first.participants:
                raise ProtocolError("mixed keygen universes in one batch")
            if s.threshold != first.threshold or s.epoch != first.epoch:
                raise ProtocolError("mixed threshold/epoch in one batch")
            if s.self_x != u_xs[self_id]:
                raise ProtocolError("share does not belong to this node")
            if len(s.vss_commitments) != s.threshold + 1:
                raise ProtocolError("missing VSS commitments on share")
            if quorum_material_digest(s) != digest0:
                raise ProtocolError("mixed Paillier material in one batch")
        if len(self.party_ids) < first.threshold + 1:
            raise ProtocolError("not enough participants for threshold")
        for pid in self.party_ids:
            if pid not in u_xs:
                raise ProtocolError("signer not in keygen universe", pid)
        if max(u_xs.values()) >= 256:
            raise ProtocolError("Shamir x beyond the setup program's 8 bits")

        aux = first.aux
        peer_pk = aux.get("peer_paillier", {})
        peer_rp = aux.get("peer_ring_pedersen", {})
        for pid in self.others():
            if pid not in peer_pk or pid not in peer_rp:
                raise ProtocolError("missing peer Paillier material", pid)

        # the widths of the ring-Pedersen combs, one pair for the whole
        # committee (every NTilde's width is public), so that a context
        # is complete when it is built and no MtaBatch has to grow it
        nt_bits = max(
            [int(aux["preparams"]["ntilde"]).bit_length()]
            + [int(peer_rp[p]["ntilde"]).bit_length() for p in self.others()]
        )
        comb_bits = gb.MtaBatch.ring_comb_bits(dom, nt_bits)

        # a context draws its randomizer base from the system's CSPRNG,
        # not from this party's rng: it may outlive the party
        def own_ctx() -> gb.PartyCtx:
            sk = PaillierPrivateKey.from_json(aux["paillier_sk"])
            rp = {k: int(v) for k, v in aux["preparams"].items()}
            pre = PreParams(
                paillier=sk, NTilde=rp["ntilde"], h1=rp["h1"], h2=rp["h2"],
                alpha=0, beta=0, P=0, Q=0,
            )
            ctx = gb.PartyCtx(self_id, pre)
            ctx.name_ring_combs(*comb_bits)
            return ctx

        def peer_ctx(pid: str):
            def build() -> gb.PartyCtx:
                prp = {k: int(v) for k, v in peer_rp[pid].items()}
                ctx = gb.PartyCtx.public(
                    pid, int(peer_pk[pid]), prp["ntilde"], prp["h1"],
                    prp["h2"],
                )
                ctx.name_ring_combs(*comb_bits)
                return ctx
            return build

        if contexts is None:
            contexts = ContextCache()  # this batch's own
        self.own = contexts.get(
            ("private", self_id, digest0, first.epoch), own_ctx)
        self.peers: Dict[str, gb.PartyCtx] = {
            pid: contexts.get(
                ("public", pid, digest0, first.epoch), peer_ctx(pid))
            for pid in self.others()
        }
        # ordered-pair MtA contexts: out = self as Alice, in = self as Bob
        self.mta_out = {
            j: gb.MtaBatch(self.own, self.peers[j], dom)
            for j in self.others()
        }
        self.mta_in = {
            j: gb.MtaBatch(self.peers[j], self.own, dom)
            for j in self.others()
        }

        # quorum Shamir data (shared across the batch: one universe)
        quorum_xs = [u_xs[p] for p in self.party_ids]
        lam = {
            pid: hm.lagrange_coeff(quorum_xs, u_xs[pid], Q)
            for pid in self.party_ids
        }
        self._w = bn.batch_to_limbs(
            [lam[self_id] * s.share % Q for s in shares], bn.P256
        )
        digs = np.stack([
            np.frombuffer(bytes(d), dtype=np.uint8) for d in digests
        ])
        if digs.shape[-1] != 32:
            raise ProtocolError("digests must be 32 bytes")
        # public per-wallet data on device: Y, every member's W_j (and
        # its compressed form), the digests as scalars
        self.Y, W_pts, W_comps, self._ok, self.m = gb.gg18_setup(
            np.stack([
                np.frombuffer(s.public_key, dtype=np.uint8) for s in shares
            ]),
            np.stack([
                np.stack([
                    np.frombuffer(c, dtype=np.uint8)
                    for c in s.vss_commitments
                ])
                for s in shares
            ]).transpose(1, 0, 2),  # (t+1, B, 33)
            digs,
            sp.scalars_to_bits([u_xs[p] for p in self.party_ids], n_bits=8),
            sp.scalars_to_bits([lam[p] for p in self.party_ids]),
        )
        self.W_pts = dict(zip(self.party_ids, W_pts))
        self._W_comp = dict(zip(self.party_ids, W_comps))
        self._binds = {pid: self._bind_row(pid) for pid in self.party_ids}
        self._m_phase = self._m_mta = None
        if metrics is not None:
            self._m_phase = metrics.histogram("party.ecdsa.phase_s")
            self._m_mta = metrics.counter("party.ecdsa.mta_responses_total")
        self._stage = 0

    # -- helpers -------------------------------------------------------------

    # binding row + block parsing come from protocol.base.BatchBlockMixin
    # (shared with batch_dkg: one definition of the security-relevant
    # session+sender binding, so the two cannot drift)
    _parse_bytes = BatchBlockMixin._parse_block

    def _phase(self, handler: str, **attrs):
        """The ``phase:gg18_*`` span of a handler's device work (``attrs``:
        what the handler adds to the span's own), and its seconds into
        ``party.ecdsa.phase_s``."""
        return _Phase(self, PHASES[handler], attrs)

    def _wide(self) -> np.ndarray:
        """(B, 40) uniform bytes: a scalar mod q once reduced (bias 2^-64)."""
        return gb.rand_bits(self.B, 320, self.rng)

    def _blocks(self, round_name: str, fields: Dict[str, int]
                ) -> Dict[str, np.ndarray]:
        """The peers' blocks of a broadcast round, stacked (q-1, B, n) in
        ``others()`` order: ``fields`` maps a payload field to its row
        width in bytes."""
        payloads = self._round_payloads(round_name)
        return {
            f: np.stack([
                self._parse_bytes(payloads[j][f], n, j)
                for j in self.others()
            ])
            for f, n in fields.items()
        }

    def _peer_binds(self) -> np.ndarray:
        return np.stack([self._binds[j] for j in self.others()])

    def _settle(self, mta: gb.MtaBatch, agg, strict) -> None:
        """The host's verdict on a batch-verified proof; a combined check
        that fails falls back to the strict per-session one (cold: its
        eager kernels compile on first use), so a bad proof is still
        attributed to its lane."""
        if gb.agg_holds(mta.alice, agg):
            return
        log.warn("batched proof check failed — strict re-verification",
                 session=self.session_id)
        self._ok = self._ok & strict()

    # -- round 1 ------------------------------------------------------------

    def start(self) -> List[RoundMsg]:
        B, q = self.B, len(self.party_ids)
        # mpcshape: unbounded-ok — B is pow-2 snapped upstream (scheduler chunks via engine/buckets.floor_bucket; bench via bucket_b)
        self._cw = compile_watch.begin("party.ecdsa", f"B{B}|q{q}")
        own = self.own
        with self._phase("start") as ph:
            self._gblind = gb.rand_bits(B, 256, self.rng)
            k_raw, gamma_raw = self._wide(), self._wide()
            u_bits = gb.rand_bit_array(B, RAND_BITS, self.rng)
            st = gb.gg18_r1_commit(
                own, k_raw, gamma_raw, self._gblind,
                self._binds[self.self_id], u_bits,
            )
            self._k, self._gamma = st["k"], st["gamma"]
            self._Gamma_own = st["Gamma"]
            self._Gamma_comp = st["Gamma_comp"]
            self._c_k = st["c_k"]
            proofs = {
                j: gb.gg18_r1_prove(
                    self.mta_out[j], st["kp"], st["c_k"], u_bits,
                    self.mta_out[j].alice_raw(B, self.rng),
                )
                for j in self.others()
            }
            ph.sync((st["ck"], proofs))
        out = [
            self.broadcast(
                R1B, {"gc": _hex(st["commit"]), "ck": _hex(st["ck"])}
            )
        ]
        for j in self.others():
            out.append(self.unicast(
                j, R1A, {f: _hex(v) for f, v in proofs[j].items()}
            ))
        self._alice_beta: Dict[str, tuple] = {}
        self._stage = 1
        return out

    # -- driver --------------------------------------------------------------

    def receive(self, msg: RoundMsg) -> List[RoundMsg]:
        if self.done:
            return []
        self._store(msg)
        others = self.others()
        out: List[RoundMsg] = []
        if (
            self._stage == 1
            and self._round_full(R1B, others)
            and self._round_full(R1A, others)
        ):
            out.extend(self._respond())
            self._stage = 2
        if self._stage == 2 and self._round_full(R2, others):
            out.append(self._delta())
            self._stage = 3
        if self._stage == 3 and self._round_full(R3, others):
            out.append(self._decommit_gamma())
            self._stage = 4
        if self._stage == 4 and self._round_full(R4, others):
            out.append(self._phase5a())
            self._stage = 5
        if self._stage == 5 and self._round_full(R5, others):
            out.append(self._phase5b())
            self._stage = 6
        if self._stage == 6 and self._round_full(R6, others):
            out.append(self._phase5c())
            self._stage = 7
        if self._stage == 7 and self._round_full(R7, others):
            out.append(self._phase5d())
            self._stage = 8
        if self._stage == 8 and self._round_full(R8, others):
            out.append(self._partial())
            self._stage = 9
        if self._stage == 9 and self._round_full(R9, others):
            self._finalize()
        return out

    # -- round 2: Bob side ---------------------------------------------------

    # a response's wire fields (per secret; the w leg adds the point U)
    _RESPONSE = ("cb", "z", "zp", "t", "v", "w", "s", "s1", "s2", "t1", "t2")

    def _respond(self) -> List[RoundMsg]:
        B = self.B
        out = []
        nb = gb.wire_bytes
        # pairs: the ordered MtA pairs answered here as Bob, one a peer
        with self._phase("_respond", pairs=len(self.others())) as ph:
            payloads, device = {}, []
            for j in self.others():
                mta = self.mta_in[j]  # alice = j, bob = self
                A = self.peers[j]
                nt_own = nb(self.own.ctx_nt.prof)
                p = self._round_payloads(R1A)[j]
                ck = self._parse_bytes(
                    self._round_payloads(R1B)[j]["ck"], nb(A.pmx.prof_n2), j
                )
                pf = {
                    "z": self._parse_bytes(p["z"], nt_own, j),
                    "u": self._parse_bytes(p["u"], nb(A.pmx.prof_n2), j),
                    "w": self._parse_bytes(p["w"], nt_own, j),
                    "s": self._parse_bytes(p["s"], nb(A.pmx.prof_n), j),
                    "s1": self._parse_bytes(p["s1"], nb(mta.p_s1), j),
                    "s2": self._parse_bytes(p["s2"], nb(mta.p_s2), j),
                }
                rho_bits = gb.rand_bit_array(B, gb.RHO_BITS, self.rng)
                c_a, self._ok, agg, (T, P, e) = gb.gg18_r2_verify(
                    mta, self._ok, ck, pf, rho_bits
                )
                self._settle(
                    mta, agg,
                    lambda: mta.bob_check_alice_strict(c_a, T, P, e),
                )
                # both secrets' responses as one 2·B-lane batch: lanes
                # [0, B) the γ leg, [B, 2B) the w leg
                blocks, U_comp, betas = gb.gg18_r2_respond(
                    mta, c_a, self._gamma, self._w,
                    mta.bob_raw(2 * B, self.rng),
                    self._W_comp[self.self_id],
                )
                device.append((blocks, U_comp))
                payload = {"w_U": U_comp}
                for f, v in blocks.items():
                    payload[f"gamma_{f}"] = _Rows(v, 0, B)
                    payload[f"w_{f}"] = _Rows(v, B, 2 * B)
                self._alice_beta[j] = betas
                payloads[j] = payload
            ph.sync(device)
        if self._m_mta is not None:
            self._m_mta.inc(2 * len(payloads) * B)
        for j, payload in payloads.items():
            out.append(self.unicast(
                j, R2, {f: _hex(v) for f, v in payload.items()}
            ))
        return out

    # -- round 3: Alice verifies + decrypts, broadcasts δ_i ------------------

    def _delta(self) -> RoundMsg:
        nb = gb.wire_bytes
        own = self.own
        widths = {
            "cb": nb(own.pmx.prof_n2), "z": nb(own.ctx_nt.prof),
            "zp": nb(own.ctx_nt.prof), "t": nb(own.ctx_nt.prof),
            "v": nb(own.pmx.prof_n2), "w": nb(own.ctx_nt.prof),
            "s": nb(own.pmx.prof_n),
        }
        # pairs: those whose answers are verified and decrypted as Alice
        with self._phase("_delta", pairs=len(self.others())) as ph:
            alphas = []
            for j in self.others():
                mta = self.mta_out[j]
                p = self._round_payloads(R2)[j]
                w_j = dict(widths, s1=nb(mta.p_s1), s2=nb(mta.p_s2),
                           t1=nb(mta.p_t1), t2=nb(mta.p_s2))
                # the peer's two responses as one 2·B-lane batch
                rs = {
                    f: np.concatenate([
                        self._parse_bytes(p[f"{name}_{f}"], w_j[f], j)
                        for name in ("gamma", "w")
                    ])
                    for f in self._RESPONSE
                }
                rho_bits = gb.rand_bit_array(
                    2 * self.B, gb.RHO_BITS, self.rng
                )
                self._ok, agg, legs, (c2, Tb, Pb, e_b) = gb.gg18_r3_verify(
                    mta, self._ok, self._c_k, rs,
                    self._parse_bytes(p["w_U"], 33, j), rho_bits,
                    self.W_pts[j], self._W_comp[j],
                )
                self._settle(
                    mta, agg,
                    lambda: _both(mta.alice_check_bob_strict(
                        c2, Tb, Pb, e_b), self.B),
                )
                alphas.append(legs)
            self._delta_own, self._sigma_own, d_block = gb.gg18_r3_delta(
                self._k, self._gamma, self._w, tuple(alphas),
                tuple(self._alice_beta[j] for j in self.others()),
            )
            ph.sync(d_block)
        return self.broadcast(R3, {"d": _hex(d_block)})

    # -- round 4: Γ decommit + Schnorr PoK -----------------------------------

    def _decommit_gamma(self) -> RoundMsg:
        with self._phase("_decommit_gamma") as ph:
            A_comp, s_pok = gb.gg18_r4_pok(
                self._wide(), self._gamma, self._Gamma_comp,
                self._binds[self.self_id],
            )
            ph.sync((A_comp, s_pok))
        return self.broadcast(
            R4,
            {
                "G": _hex(self._Gamma_comp),
                "blind": _hex(self._gblind),
                "A": _hex(A_comp),
                "spok": _hex(s_pok),
            },
        )

    # -- round 5A ------------------------------------------------------------

    def _phase5a(self) -> RoundMsg:
        with self._phase("_phase5a") as ph:
            peers = self._blocks(
                R4, {"G": 33, "blind": 32, "A": 33, "spok": 32}
            )
            peers["gc"] = self._blocks(R1B, {"gc": 32})["gc"]
            peers["d"] = self._blocks(R3, {"d": 32})["d"]
            peers["bind"] = self._peer_binds()
            raw = {x: self._wide() for x in ("li", "rho", "ka", "kb")}
            self._va_blind = gb.rand_bits(self.B, 256, self.rng)
            ok, delta, Gamma_sum = gb.gg18_r5a_verify(
                self._ok, self._delta_own, self._Gamma_own, peers
            )
            st = gb.gg18_r5a_commit(
                ok, delta, Gamma_sum, self.m, self._k, self._sigma_own,
                raw, self._va_blind, self._binds[self.self_id],
            )
            self._ok = st["ok"]
            self._R_pt, self._r, self._rec = st["R"], st["r"], st["rec"]
            self._li, self._rho = st["li"], st["rho"]
            self._ka, self._kb = st["ka"], st["kb"]
            self._s_own, self._V_own, self._A_own = st["s"], st["V"], st["A"]
            self._vc, self._ac = st["vc"], st["ac"]
            ph.sync(st["commit"])
        return self.broadcast(R5, {"c": _hex(st["commit"])})

    # -- round 5B ------------------------------------------------------------

    def _phase5b(self) -> RoundMsg:
        with self._phase("_phase5b") as ph:
            Apok, sa, sb = gb.gg18_r5b(
                self._ka, self._kb, self._s_own, self._li, self._R_pt,
                self._vc, self._ac, self._binds[self.self_id],
            )
            ph.sync((Apok, sa, sb))
        return self.broadcast(
            R6,
            {
                "vc": _hex(self._vc),
                "ac": _hex(self._ac),
                "blind": _hex(self._va_blind),
                "apok": _hex(Apok),
                "sa": _hex(sa),
                "sb": _hex(sb),
            },
        )

    # -- round 5C ------------------------------------------------------------

    def _phase5c(self) -> RoundMsg:
        with self._phase("_phase5c") as ph:
            peers = self._blocks(
                R6, {"vc": 33, "ac": 33, "blind": 32, "apok": 33,
                     "sa": 32, "sb": 32},
            )
            peers["c"] = self._blocks(R5, {"c": 32})["c"]
            peers["bind"] = self._peer_binds()
            self._ut_blind = gb.rand_bits(self.B, 256, self.rng)
            self._ok, V_sum, A_sum = gb.gg18_r5c_verify(
                self._ok, self._V_own, self._A_own, self._R_pt, peers
            )
            st = gb.gg18_r5c_commit(
                V_sum, A_sum, self.m, self._r, self.Y, self._rho,
                self._li, self._ut_blind, self._binds[self.self_id],
            )
            self._U_own, self._T_own = st["U"], st["T"]
            self._uc, self._tc = st["uc"], st["tc"]
            ph.sync(st["commit"])
        return self.broadcast(R7, {"c": _hex(st["commit"])})

    # -- round 5D ------------------------------------------------------------

    def _phase5d(self) -> RoundMsg:
        # a reveal: no program runs, the span holds the blocks' way to
        # the host
        with self._phase("_phase5d"):
            payload = {
                "uc": _hex(self._uc),
                "tc": _hex(self._tc),
                "blind": _hex(self._ut_blind),
            }
        return self.broadcast(R8, payload)

    # -- round 5E ------------------------------------------------------------

    def _partial(self) -> RoundMsg:
        with self._phase("_partial") as ph:
            peers = self._blocks(R8, {"uc": 33, "tc": 33, "blind": 32})
            peers["c"] = self._blocks(R7, {"c": 32})["c"]
            peers["bind"] = self._peer_binds()
            self._ok, s_block = gb.gg18_r5e(
                self._ok, self._U_own, self._T_own, self._s_own, peers
            )
            ph.sync(s_block)
        return self.broadcast(R9, {"s": _hex(s_block)})

    def _finalize(self) -> None:
        # One program over the whole batch. (The counter-phase cohorts of
        # engine/pipeline overlapped one cohort's signature egress with
        # the next one's verification; with the egress four small arrays
        # there is nothing left to overlap, and K programs to compile.)
        with self._phase("_finalize") as ph:
            r, s, rec, ok = gb.gg18_final(
                self._ok, self._s_own, self._blocks(R9, {"s": 32})["s"],
                self.m, self._r, self._rec, self.Y,
            )
            ph.sync(ok)
        self.result = {
            "r": np.asarray(r),  # mpcflow: host-ok — signature egress
            "s": np.asarray(s),  # mpcflow: host-ok — signature egress
            "recovery": np.asarray(rec),  # mpcflow: host-ok — signature egress
            "ok": np.asarray(ok),  # mpcflow: host-ok — per-wallet verdicts, egress with the signatures
        }
        self.done = True
        compile_watch.finish(self._cw)


class _Phase:
    """A handler's ``phase:gg18_<name>`` span (attributes ``batch``, ``n``,
    ``q``: the party's signers, fewer than the committee while a node is
    out; ``cohort``; ``cpu_s``: the handler's own CPU seconds, the rest of
    the span being the device wait it ends in; and what the handler adds:
    ``pairs`` on the two MtA phases; a child of the ``round:`` span open on
    this thread), ended by ``sync`` of the handler's device results only
    while tracing is armed, and its seconds into the node's
    ``party.ecdsa.phase_s``."""

    def __init__(self, party: BatchedECDSASigningParty, name: str,
                 attrs: dict):
        self._party = party
        self._name = f"phase:gg18_{name}"
        self._attrs = attrs

    def __enter__(self) -> "_Phase":
        party = self._party
        self._t0 = tracing.now_ns()
        self._span = tracing.span(
            self._name, batch=party.session_id.removeprefix("bsign:"),
            n=party.B, q=len(party.party_ids), cohort=0, cpu=True,
            **self._attrs,
        )
        self._span.__enter__()
        return self

    sync = staticmethod(_span_sync)

    def __exit__(self, *exc) -> None:
        self._span.__exit__(*exc)
        if self._party._m_phase is not None:
            self._party._m_phase.observe((tracing.now_ns() - self._t0) / 1e9)
