"""Distributed batched threshold-Ed25519 signing: ONE protocol instance
signs B wallets' digests concurrently.

This is the node-side face of the TPU batch engine (SURVEY.md §7.2 step 5):
where :mod:`.signing` runs one session per wallet (per-session goroutine
concurrency in the reference, event_consumer.go:295-338), this party
exchanges fixed-shape BYTE BLOCKS — (B·32)-byte commitment/nonce/partial
blocks — and computes each round with one :mod:`engine.eddsa_batch`
dispatch. The scheduler (consumers.batch_scheduler) buckets concurrent
signing requests into these batches.

Protocol (same 3-round commit–reveal threshold Schnorr as .signing, over
the batch):

  R1 (broadcast) hash commitment to this party's (B, 32) nonce block
  R2 (broadcast) decommit: nonce block + blind
  R3 (broadcast) partial-signature block (B, 32)
  finalize       combine + batched RFC 8032 verification → per-session ok

A failed session (bad point, verification miss) fails ONLY its lane: the
result carries a per-session ok mask so the scheduler can emit per-tx
success/error events. Commitment fraud aborts the whole batch with the
culprit attributed (same abort semantics as the per-session protocol).
"""
from __future__ import annotations

import hashlib
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...core import bignum as bn
from ...core import hostmath as hm
from ...engine import eddsa_batch as eb
from ...engine import pipeline as pl
from ...ops import hash_suite as hs
from ...perf import compile_watch
from ...utils import tracing
from ..base import KeygenShare, PartyBase, ProtocolError, RoundMsg, party_xs

R1_COMMIT = "eddsa/bsign/1/commit"
R2_REVEAL = "eddsa/bsign/2/reveal"
R3_PARTIAL = "eddsa/bsign/3/partial"


def _block_commit(blind: bytes, block: bytes, bind: bytes) -> str:
    return hashlib.sha256(
        b"mpcium-tpu/bsign/" + bind + blind + block
    ).hexdigest()


def _span_sync(tensors) -> None:
    """Materialize a cohort's device-phase result before its span closes
    so the interval is honest device time — only when tracing is armed
    (untraced runs never sync here; engine PhaseTimer discipline)."""
    if tracing.enabled():
        jax.block_until_ready(tensors)  # mpcflow: host-ok — trace instrumentation, only when tracing is armed


class BatchedEDDSASigningParty(PartyBase):
    """One signer's side of a B-session batch.

    ``shares``: this node's key shares, one per wallet (batch order is the
    manifest order, identical on every quorum member). ``messages``: the
    B raw messages to sign (RFC 8032 has no prehash: the challenge is
    SHA-512(R ‖ A ‖ M) over the message as it came), of any lengths,
    which may differ inside the batch; they are packed for the device
    hash once, here (``eb.pack_messages``). All wallets must share the
    signing quorum (``party_ids``); universes may differ per wallet (λ is
    computed per wallet from its own keygen universe).

    ``metrics``: the node's registry (the scheduler hands its own):
    counters ``party.eddsa.hash_blocks_total`` (128-byte blocks the
    device compressed for challenges: lanes × the rung) and
    ``party.eddsa.host_hash_rows_total`` (challenges hashed on the host:
    MPCIUM_EDDSA_DEVICE_HASH=0, or a message past the top rung); none,
    nothing is counted.
    """

    def __init__(
        self,
        session_id: str,
        self_id: str,
        party_ids: Sequence[str],
        shares: Sequence[KeygenShare],
        messages: Sequence[bytes],
        rng=None,
        cohorts: Optional[int] = None,
        metrics=None,
    ):
        import secrets as _secrets

        super().__init__(session_id, self_id, party_ids, rng or _secrets)
        self._cohorts = cohorts
        if len(shares) != len(messages) or not shares:
            raise ValueError("one share per message required")
        self.B = len(shares)
        self.messages = [bytes(m) for m in messages]
        # (M, lens) for the device challenge hash; None: hashed on the host
        self._packed = (
            eb.pack_messages(self.messages)
            if eb.device_hash_enabled() else None
        )
        self._msg_lens = np.fromiter(
            (len(m) for m in self.messages), np.int64, self.B
        )
        self._m_hash_blocks = self._m_host_rows = None
        if metrics is not None:
            self._m_hash_blocks = metrics.counter(
                "party.eddsa.hash_blocks_total")
            self._m_host_rows = metrics.counter(
                "party.eddsa.host_hash_rows_total")
        lamx = []
        for s in shares:
            if s.key_type != "ed25519":
                raise ProtocolError("wrong key type for EdDSA batch signing")
            if len(party_ids) < s.threshold + 1:
                raise ProtocolError("not enough participants for threshold")
            xs = party_xs(s.participants)
            for pid in party_ids:
                if pid not in xs:
                    raise ProtocolError("signer not in keygen universe", pid)
            if xs[self_id] != s.self_x:
                raise ProtocolError("share does not belong to this node")
            quorum_xs = [xs[p] for p in self.party_ids]
            lam = hm.lagrange_coeff(quorum_xs, xs[self_id], hm.ED_L)
            lamx.append(lam * s.share % hm.ED_L)
        self.lamx = eb.scalars_to_limb_batch(lamx)
        self.A_comp = np.stack(
            [np.frombuffer(s.public_key, dtype=np.uint8) for s in shares]
        )
        self._stage = 0

    # -- rounds --------------------------------------------------------------

    def _bind(self) -> bytes:
        return f"{self.session_id}:{self.self_id}".encode()

    def start(self) -> List[RoundMsg]:
        # party-level compile signature: the whole 3-round session is one
        # shape bucket — first session per (B, q) pays the warmup, later
        # ones cost a set lookup (engine-level begin sites nest inside)
        B, q = self.B, len(self.party_ids)
        # mpcshape: unbounded-ok — B is pow-2 snapped upstream (scheduler chunks via engine/buckets.floor_bucket; bench via bucket_b)
        self._cw = compile_watch.begin("party.eddsa", f"B{B}|q{q}")
        # counter-phase cohort schedule (engine/pipeline): nonces for the
        # FULL batch are drawn first in K=1 serial order, then row-sliced
        # per cohort, so wire blocks are bit-identical for every K
        self._plan = pl.CohortPlan.for_batch(B, self._cohorts)
        r64 = eb.fresh_nonce_bytes(self.B, self.rng)

        # device-phase spans: each cohort's round syncs its result before
        # the span closes (only when traced), so the interval is honest
        # device time; byte packing runs as a host:* pipeline stage
        def make_job(ci: int, sl: slice):
            def job():
                with tracing.span(
                    "phase:bsign_nonce_commit",
                    batch=sl.stop - sl.start, cohort=ci,
                    q=len(self.party_ids), cpu=True,
                ):
                    r_limbs, R_comp = eb.nonce_commitments(eb.to_dev(r64[sl]))
                    _span_sync(R_comp)
                block = yield (
                    "nonce_egress",
                    lambda: np.asarray(R_comp).tobytes(),
                )
                return r_limbs, block

            return job

        outs = pl.run_counter_phase(
            [make_job(ci, sl) for ci, sl in enumerate(self._plan.slices())]
        )
        self._r_limbs_c = [r for r, _ in outs]
        self._R_block = b"".join(blk for _, blk in outs)  # B·32 bytes
        self._blind = self.rng.token_bytes(32)
        commit = _block_commit(self._blind, self._R_block, self._bind())
        self._stage = 1
        return [self.broadcast(R1_COMMIT, {"commit": commit})]

    def receive(self, msg: RoundMsg) -> List[RoundMsg]:
        if self.done:
            return []
        self._store(msg)
        others = self.others()
        out: List[RoundMsg] = []
        if self._stage == 1 and self._round_full(R1_COMMIT, others):
            out.append(
                self.broadcast(
                    R2_REVEAL,
                    {"R": self._R_block.hex(), "blind": self._blind.hex()},
                )
            )
            self._stage = 2
        if self._stage == 2 and self._round_full(R2_REVEAL, others):
            out.append(self._round3())
            self._stage = 3
        if self._stage == 3 and self._round_full(R3_PARTIAL, others):
            self._finalize()
        return out

    def _peer_blocks(self, round_name: str, field: str, nbytes: int) -> Dict[str, bytes]:
        payloads = self._round_payloads(round_name)
        out = {}
        for pid, p in payloads.items():
            b = bytes.fromhex(p[field])
            if len(b) != nbytes:
                raise ProtocolError(f"bad {field} block size", pid)
            out[pid] = b
        return out

    def _round3(self) -> RoundMsg:
        commits = self._round_payloads(R1_COMMIT)
        reveals = self._round_payloads(R2_REVEAL)
        R_blocks: List[bytes] = []
        for pid in self.party_ids:
            if pid == self.self_id:
                R_blocks.append(self._R_block)
                continue
            blk = bytes.fromhex(reveals[pid]["R"])
            if len(blk) != self.B * 32:
                raise ProtocolError("bad nonce block size", pid)
            bind = f"{self.session_id}:{pid}".encode()
            if (
                _block_commit(bytes.fromhex(reveals[pid]["blind"]), blk, bind)
                != commits[pid]["commit"]
            ):
                raise ProtocolError("nonce commitment fraud", pid)
            R_blocks.append(blk)
        R_all = np.stack(
            [np.frombuffer(b, dtype=np.uint8).reshape(self.B, 32) for b in R_blocks]
        )

        packed = self._packed
        # 128-byte blocks a lane of this batch costs the device hash
        rung = 0 if packed is None else hs.sha512_masked_blocks(
            64 + packed[0].shape[1])

        def make_job(ci: int, sl: slice):
            def job():
                lanes = sl.stop - sl.start
                with tracing.span(
                    "phase:bsign_aggregate_partial",
                    batch=lanes, cohort=ci,
                    q=len(self.party_ids), cpu=True,
                    msg_bytes=int(self._msg_lens[sl].sum()),
                    hash_blocks=lanes * rung,
                ):
                    R_sum, ok_R = eb.aggregate_nonce(
                        eb.to_dev(R_all[:, sl], axis=1)
                    )
                    A_c = eb.to_dev(self.A_comp[sl])
                    if packed is not None:
                        c64 = eb.challenge_device(
                            R_sum, A_c, eb.to_dev(packed[0][sl]),
                            packed[1][sl],
                        )
                    else:
                        R_sum_h = np.asarray(R_sum)  # mpcflow: host-ok — host challenge hash (MPCIUM_EDDSA_DEVICE_HASH=0, or a message past the top rung), counted in party.eddsa.host_hash_rows_total
                        c64 = eb.to_dev(eb.challenge_hashes(
                            R_sum_h, self.A_comp[sl], self.messages[sl]
                        ))
                    parts = eb.partial_signature(
                        self._r_limbs_c[ci], c64, eb.to_dev(self.lamx[sl]),
                    )
                    _span_sync(parts)
                if packed is None:
                    if self._m_host_rows is not None:
                        self._m_host_rows.inc(lanes)
                elif self._m_hash_blocks is not None:
                    self._m_hash_blocks.inc(lanes * rung)
                egress = yield (
                    "partial_egress",
                    lambda: (
                        np.asarray(bn.limbs_to_bytes_le(parts, bn.P256, 32)),
                        np.asarray(ok_R),
                    ),
                )
                return (R_sum, A_c, c64), parts, egress

            return job

        outs = pl.run_counter_phase(
            [make_job(ci, sl) for ci, sl in enumerate(self._plan.slices())]
        )
        # R, A and the challenge stay on the device for the last round
        self._dev_c = [o[0] for o in outs]
        self._parts_c = [o[1] for o in outs]
        self._ok_R = pl.merge_rows([o[2][1] for o in outs])
        s_block = pl.merge_rows([o[2][0] for o in outs])
        return self.broadcast(R3_PARTIAL, {"s": s_block.tobytes().hex()})

    def _finalize(self) -> None:
        blocks = self._peer_blocks(R3_PARTIAL, "s", self.B * 32)
        peer_rows = {
            pid: np.frombuffer(blocks[pid], dtype=np.uint8).reshape(self.B, 32)
            for pid in self.party_ids
            if pid != self.self_id
        }

        def make_job(ci: int, sl: slice):
            def job():
                with tracing.span(
                    "phase:bsign_combine_verify",
                    batch=sl.stop - sl.start, cohort=ci,
                    q=len(self.party_ids), cpu=True,
                ):
                    stacked = [self._parts_c[ci]]
                    for pid in self.party_ids:
                        if pid == self.self_id:
                            continue
                        stacked.append(
                            bn.bytes_to_limbs_le(
                                jnp.asarray(peer_rows[pid][sl]),
                                bn.P256, bn.P256.n_limbs,
                            )
                        )
                    parts = jnp.stack(stacked)
                    R_sum, A_c, c64 = self._dev_c[ci]
                    sigs, _s = eb.combine_signatures(parts, R_sum)
                    ok = eb.verify_signatures(sigs, A_c, c64)
                    _span_sync(ok)
                egress = yield (
                    "sig_egress",
                    lambda: (np.asarray(sigs), np.asarray(ok)),
                )
                return egress

            return job

        outs = pl.run_counter_phase(
            [make_job(ci, sl) for ci, sl in enumerate(self._plan.slices())]
        )
        self.result = {
            "signatures": pl.merge_rows([o[0] for o in outs]),
            "ok": pl.merge_rows([o[1] for o in outs]) & self._ok_R,
        }
        self.done = True
        compile_watch.finish(self._cw)
