"""Batched threshold-Ed25519 signing: the TPU execution engine.

This is the framework's replacement for the reference's per-session
goroutine concurrency (SURVEY.md §2.2 dimension 2 → the session batch
axis): each MPC party coalesces the round compute of B concurrent signing
sessions into single fixed-shape XLA dispatches. The protocol is the same
commit–reveal threshold Schnorr as ``protocol.eddsa.signing`` (3 rounds,
matching reference pkg/mpc/eddsa_rounds.go:23-25); here the per-round math
runs on device over ``(B, …)`` tensors, and since the device hash suite
(ops.hash_suite) the hashing does too: commitments batch through the
SHA-256 kernel and the RFC 8032 challenge through the 64-bit-lane
SHA-512 kernel, over the raw messages whatever their lengths (lengths are
data to that kernel, never a compile), so the round tensors never
round-trip through the host (MPCIUM_EDDSA_DEVICE_HASH=0 restores the
native/hashlib path).

Wire format for batched rounds is *byte tensors*, not JSON: a party's
round-1 message is the (B, 32) array of compressed nonce commitments, etc.
Device-side pack/unpack (`bignum.bytes_to_limbs_le`) keeps the host out of
the hot loop.

Every public function is shape-stable: jit caches one executable per batch
size. Use powers of two (pad the tail of a partial batch with dummy
sessions; the `ok` masks make padding harmless).
"""
from __future__ import annotations

import hashlib
import os
import secrets
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import bignum as bn
from ..core import ed25519_jax as ed
from ..core import hostmath as hm
from ..core.bignum import P256 as PROF
from ..ops import hash_suite as hs
from ..perf import compile_watch
from ..utils import tracing


def _trace_sync(tensors) -> None:
    """Phase-boundary sync for mpctrace phase timers — reached only when
    tracing is armed (untraced runs never sync here)."""
    jax.block_until_ready(tensors)  # mpcflow: host-ok — trace instrumentation, only when tracing is armed

# 512-bit inputs (hash outputs / wide nonces) occupy 43 twelve-bit limbs —
# within BarrettCtx.reduce's 2n = 44-limb bound.
_WIDE_LIMBS = 43

# Session-axis sharding (engine/sharded.py arms this): when a mesh is
# armed, every batch tensor entering the engine is placed with its
# leading (session) axis partitioned over the local devices, and GSPMD
# partitions every downstream dispatch — a multi-device host then runs
# each party-round across all its chips with no kernel changes
# (SURVEY.md §2.2 dimension 2). None ⇒ plain single-device placement.
_SESSION_SHARDING = None
# how many tensors entered the engine on the DEFAULT device while a mesh
# was armed (session axis not divisible by the mesh): legal for small
# batches, but a full-width wave that counts any is running on one chip
_UNSHARDED_PLACEMENTS = 0


def arm_session_sharding(sharding) -> None:
    """Install (or clear, with None) the NamedSharding applied by
    :func:`to_dev`, and zero the unsharded-placement count. Called by
    engine.sharded.arm_session_axis()."""
    global _SESSION_SHARDING, _UNSHARDED_PLACEMENTS
    _SESSION_SHARDING = sharding
    _UNSHARDED_PLACEMENTS = 0


def unsharded_placements() -> int:
    """Tensors :func:`to_dev` placed on the default device since the
    mesh was armed because their session axis did not divide it."""
    return _UNSHARDED_PLACEMENTS


def to_dev(x, axis: int = 0) -> jnp.ndarray:
    """Engine ingress: jnp.asarray plus the armed session sharding on
    ``axis`` — callers MUST name the axis that is the session batch
    (round tensors like (q, B, 32) are party-leading: sharding axis 0
    there would partition the committee, forcing cross-device gathers in
    the aggregations). Axes that don't divide the mesh fall back to
    default placement rather than failing the dispatch (sub-mesh batches
    are legal traffic); each fallback is counted
    (:func:`unsharded_placements`)."""
    global _UNSHARDED_PLACEMENTS
    arr = jnp.asarray(x)
    s = _SESSION_SHARDING
    if s is None or arr.ndim <= axis:
        return arr
    n = s.mesh.devices.size
    if arr.shape[axis] % n != 0:
        _UNSHARDED_PLACEMENTS += 1
        return arr
    if axis == 0:
        return jax.device_put(arr, s)
    from jax.sharding import NamedSharding, PartitionSpec

    spec = PartitionSpec(*([None] * axis + list(s.spec)))
    return jax.device_put(arr, NamedSharding(s.mesh, spec))


def _reduce_wide(b64: jnp.ndarray) -> jnp.ndarray:
    """(…, 64) uint8 little-endian → canonical scalar limbs mod l."""
    L = ed.scalar_ring()
    return L.reduce(bn.bytes_to_limbs_le(b64, PROF, _WIDE_LIMBS))


# ---------------------------------------------------------------------------
# jitted round kernels (party-local, batched over sessions)
# ---------------------------------------------------------------------------


@jax.jit
def nonce_commitments(r64: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Round 1 compute. ``r64``: (..., 64) uint8 of fresh CSPRNG bytes.

    Returns (r_limbs mod l, compressed R_i = r·B as (..., 32) uint8).
    The 512→252-bit reduction makes the nonce statistically uniform mod l
    (RFC 8032's own wide-reduction trick).
    """
    r = _reduce_wide(r64)
    R = ed.base_mul(bn.limbs_to_bits(r, PROF, ed.SCALAR_BITS))
    return r, ed.compress(R)


@jax.jit
def aggregate_nonce(R_all: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(q, B, 32) compressed nonce shares → ((B, 32) compressed R = Σ R_i,
    (B,) validity mask). Decompression + point adds on device."""
    pts, ok = ed.decompress(R_all)
    acc = ed.EdPointJ(pts.X[0], pts.Y[0], pts.Z[0], pts.T[0])
    for i in range(1, R_all.shape[0]):
        acc = ed.add(acc, ed.EdPointJ(pts.X[i], pts.Y[i], pts.Z[i], pts.T[i]))
    return ed.compress(acc), jnp.all(ok, axis=0)


@jax.jit
def partial_signature(
    r_limbs: jnp.ndarray, c64: jnp.ndarray, lamx_limbs: jnp.ndarray
) -> jnp.ndarray:
    """Round 3 compute: s_i = r + H(R‖A‖M)·λ_i·x_i (mod l), batched.

    ``c64``: raw SHA-512 digests (B, 64); ``lamx_limbs``: λ_i·x_i mod l as
    limbs (λ from the keygen-universe x-coords; see protocol.eddsa.signing).
    """
    L = ed.scalar_ring()
    c = _reduce_wide(c64)
    return L.addmod(r_limbs, L.mulmod(c, lamx_limbs))


@jax.jit
def combine_signatures(
    s_parts: jnp.ndarray, R_comp: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(q, B, 22) partial-sig limbs + (B, 32) R → ((B, 64) signatures,
    (B, 22) s limbs). Signature layout per RFC 8032: R ‖ s little-endian."""
    L = ed.scalar_ring()
    s = s_parts[0]
    for i in range(1, s_parts.shape[0]):
        s = L.addmod(s, s_parts[i])
    s_bytes = bn.limbs_to_bytes_le(s, PROF, 32)
    return jnp.concatenate([R_comp, s_bytes], axis=-1), s


@jax.jit
def verify_signatures(
    sig: jnp.ndarray, A_comp: jnp.ndarray, c64: jnp.ndarray
) -> jnp.ndarray:
    """Batched RFC 8032 verification given precomputed challenge hashes:
    s·B == R + c·A. Returns (B,) bool. (The challenge c64 = SHA512(R‖A‖M)
    is hashed host-side; everything else runs on device.)"""
    L = ed.scalar_ring()
    pts, ok_pts = ed.decompress(jnp.stack([sig[..., :32], A_comp]))
    R_pt, A_pt = (ed.EdPointJ(*(c[i] for c in pts)) for i in (0, 1))
    s = bn.bytes_to_limbs_le(sig[..., 32:], PROF, PROF.n_limbs)
    l_l = jnp.broadcast_to(jnp.asarray(bn.to_limbs(hm.ED_L, PROF)), s.shape)
    ok_range = bn.compare(s, l_l) < 0
    c = _reduce_wide(c64)
    lhs = ed.base_mul(bn.limbs_to_bits(s, PROF, ed.SCALAR_BITS))
    rhs = ed.add(R_pt, ed.scalar_mul(bn.limbs_to_bits(c, PROF, ed.SCALAR_BITS), A_pt))
    return ed.equal(lhs, rhs) & jnp.all(ok_pts, axis=0) & ok_range


@jax.jit
def fused_sign_step(
    r64: jnp.ndarray, c64: jnp.ndarray, lamx_limbs: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The whole device side of one batched signing step in ONE dispatch:
    nonce scalars + commitments, nonce aggregation, partial signatures,
    combine. ``r64`` (q, B, 64); ``c64`` (B, 64) challenge hashes;
    ``lamx_limbs`` (q, B, 22). Returns ((B, 64) signatures, (B,) R-valid).

    This is the single-chip flagship step (__graft_entry__.entry): in the
    two-phase production flow the challenge is hashed between nonce
    aggregation and partials, but the fused form is what one party executes
    when replaying a round pipeline whose hashes are already known.
    """
    q = r64.shape[0]
    r, R_comp = nonce_commitments(r64)
    R_sum, ok_R = aggregate_nonce(R_comp)
    parts = partial_signature(r, jnp.broadcast_to(c64, (q,) + c64.shape), lamx_limbs)
    sigs, _ = combine_signatures(parts, R_sum)
    return sigs, ok_R


# ---------------------------------------------------------------------------
# donated round steps (counter-phase cohort pipeline, engine/pipeline.py)
# ---------------------------------------------------------------------------
#
# Per-round session state is an explicit carried pytree and every step
# DONATES its input state (donate_argnums=(0,)): XLA reuses or frees the
# previous round's buffers instead of keeping both rounds live, which is
# the HBM headroom that makes B=16384 viable (engine/buckets.py). The
# donation contract for callers: rebind, never re-read — ``st =
# round_step_x(st)``; mpcshape rule MPS906 flags any read of a donated
# binding after the call site. Chaining step-to-step keeps the state on
# device with its ingress sharding (to_dev's session axis), so cohort
# handoffs never reshard.


@partial(jax.jit, donate_argnums=(0,))
def round_step_nonce(st, pref):
    """R1 as one donated step: ``{r64 (q,B,64), blinds (q,B,32)}`` →
    ``{r, R_comp, commit_msg, commits}``. Same kernel composition as the
    unpipelined path (nonce_commitments + device SHA-256 commitments) —
    bit-identical outputs, one dispatch."""
    r, R_comp = nonce_commitments(st["r64"])
    q, B = R_comp.shape[0], R_comp.shape[1]
    commit_msg = jnp.concatenate(
        [jnp.broadcast_to(pref, (q, B) + pref.shape), st["blinds"], R_comp],
        axis=-1,
    )
    return {
        "r": r,
        "R_comp": R_comp,
        "commit_msg": commit_msg,
        "commits": hs.sha256(commit_msg),
    }


@partial(jax.jit, donate_argnums=(0,))
def round_step_aggregate(st):
    """R2 as one donated step: re-hash the received commitment tensors
    (one fraud verdict for the batch) and aggregate the nonce points."""
    again = hs.sha256(st["commit_msg"])
    R_sum, ok_R = aggregate_nonce(st["R_comp"])
    return {
        "r": st["r"],
        "R_sum": R_sum,
        "ok_R": ok_R,
        "fraud_free": jnp.all(again == st["commits"]),
    }


@partial(jax.jit, donate_argnums=(0,))
def round_step_partial(st, c64, lamx):
    """R3 as one donated step: partial signatures + combine."""
    q = st["r"].shape[0]
    parts = partial_signature(
        st["r"], jnp.broadcast_to(c64, (q,) + c64.shape), lamx
    )
    sigs, _ = combine_signatures(parts, st["R_sum"])
    return {"sigs": sigs, "ok_R": st["ok_R"], "R_sum": st["R_sum"]}


# ---------------------------------------------------------------------------
# host helpers
# ---------------------------------------------------------------------------


def device_hash_enabled() -> bool:
    """MPCIUM_EDDSA_DEVICE_HASH gates the device hash path (default ON):
    commitments and the RFC 8032 challenge hash through ops.hash_suite's
    SHA-256/SHA-512 kernels where the round tensors already live. Set to
    0 to restore the native C++ / hashlib host path (which stays the
    reference oracle — all paths are byte-identical)."""
    return os.environ.get("MPCIUM_EDDSA_DEVICE_HASH", "1") != "0"


def pack_messages(
    messages: Sequence[bytes],
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """The batch's messages as the device challenge hash takes them:
    ``(M, lens)`` with ``M`` (B, cap − 64) uint8, each row its message
    zero-filled to the width of the SHA-512 rung that holds the longest
    ``R ‖ A ‖ M`` (``hash_suite.sha512_rung_cap``), and ``lens`` (B,)
    int32 the message lengths. Lengths are data to the hash program: two
    batches on one rung compile once whatever their lengths. ``None``
    where a message is past the top rung (1,967 bytes): the caller hashes
    on the host."""
    lens = np.fromiter((len(m) for m in messages), np.int32, len(messages))
    cap = hs.sha512_rung_cap(64 + int(lens.max(initial=0)))
    if cap is None:
        return None
    width = cap - 64
    M = np.frombuffer(
        b"".join(m.ljust(width, b"\0") for m in messages), np.uint8
    ).reshape(len(messages), width)
    return M, lens


def challenge_device(R_comp, A_comp, M, lens=None) -> jnp.ndarray:
    """Device challenge hashes: SHA-512(R ‖ A ‖ M) over (B, 32)/(B, 32)/
    (B, W) uint8 rows (device or host) → (B, 64) device digests, through
    the masked 64-bit-lane kernel (``hash_suite.sha512_masked``). ``lens``
    (B,) int32 on the host: each row's message length, the rows
    zero-filled past it and ``W`` a rung's width (:func:`pack_messages`
    makes both); without it every row is ``W`` bytes long. The batch
    engine and the served party call this directly so R and c64 never
    leave the device."""
    if lens is None:
        B, W = M.shape
        lens = np.full((B,), W, np.int32)
        cap = hs.sha512_rung_cap(64 + W)
        if cap is None:
            raise ValueError(f"a {W}-byte message is past the top rung")
        M = jnp.pad(jnp.asarray(M), ((0, 0), (0, cap - 64 - W)))
    rows = jnp.concatenate(
        [jnp.asarray(R_comp), jnp.asarray(A_comp), jnp.asarray(M)], axis=-1
    )
    return hs.sha512_masked(rows, to_dev(lens + 64))


def challenge_hashes(
    R_comp: np.ndarray, A_comp: np.ndarray, messages: Sequence[bytes]
) -> np.ndarray:
    """Per-session SHA-512(R ‖ A ‖ M) → (B, 64) uint8 on the host.

    Messages of any lengths up to the top rung hash on the device as one
    dispatch (:func:`challenge_device`). MPCIUM_EDDSA_DEVICE_HASH=0 and a
    message past the top rung hash on the host: the native C++ batch call
    for equal lengths, else one ``hashlib`` call a row, the reference
    every path is held to byte for byte (tests/test_hash_suite.py,
    tests/test_eddsa_batch.py).
    """
    from .. import native

    packed = pack_messages(messages) if device_hash_enabled() else None
    if packed is not None:
        return np.asarray(challenge_device(R_comp, A_comp, *packed))  # mpcflow: host-ok — host-facing helper egress; the batch engine uses challenge_device and keeps c64 on device
    R = np.asarray(R_comp)  # mpcflow: host-ok — host hash (MPCIUM_EDDSA_DEVICE_HASH=0, or a message past the top rung): the host hashers read host rows
    A = np.asarray(A_comp)  # mpcflow: host-ok — host hash (MPCIUM_EDDSA_DEVICE_HASH=0, or a message past the top rung): the host hashers read host rows
    lens = {len(m) for m in messages}
    if len(lens) == 1:
        M = np.frombuffer(b"".join(messages), dtype=np.uint8).reshape(
            len(messages), lens.pop()
        )
        return native.batch_sha512(b"", np.concatenate([R, A, M], axis=1))
    out = np.empty((len(messages), 64), dtype=np.uint8)
    for i, m in enumerate(messages):
        out[i] = np.frombuffer(
            hashlib.sha512(R[i].tobytes() + A[i].tobytes() + m).digest(),
            dtype=np.uint8,
        )
    return out


def fresh_nonce_bytes(batch: int, rng=secrets) -> np.ndarray:
    """(B, 64) CSPRNG bytes for round 1."""
    return np.frombuffer(rng.token_bytes(batch * 64), dtype=np.uint8).reshape(
        batch, 64
    )


def scalars_to_limb_batch(xs: Sequence[int]) -> np.ndarray:
    """Host scalars (already reduced mod l) → (B, 22) int32."""
    return bn.batch_to_limbs([x % hm.ED_L for x in xs], PROF)


# ---------------------------------------------------------------------------
# in-process co-signing fabric (bench / tests / loopback deployments)
# ---------------------------------------------------------------------------


class BatchedCoSigners:
    """Drives q parties × B sessions of the 3-round signing protocol with
    batched device compute per party per round — the measurement harness for
    the throughput north star (SURVEY.md §6) and the reference
    implementation for the distributed node's batched rounds.

    ``party_shares``: for each of the q quorum parties, that party's
    per-session key shares (length B, same wallet order). All sessions must
    share one quorum topology (same party ids / x-coords); mixed topologies
    belong in separate batches (the engine buckets by topology).
    """

    def __init__(
        self,
        party_ids: Sequence[str],
        party_shares: Sequence[Sequence["KeygenShare"]],  # noqa: F821
        rng=secrets,
    ):
        from ..protocol.base import party_xs

        assert len(party_ids) == len(party_shares) >= 2
        self.party_ids = list(party_ids)
        self.q = len(party_ids)
        self.B = len(party_shares[0])
        assert all(len(s) == self.B for s in party_shares)
        self.rng = rng

        first = party_shares[0][0]
        if self.q < first.threshold + 1:
            raise ValueError("not enough participants for threshold")
        universe_xs = party_xs(first.participants)
        quorum_xs = [universe_xs[p] for p in party_ids]
        # λ_i·x_i per (party, session): λ depends only on the quorum
        # topology, shared across the batch
        self.lamx = np.empty((self.q, self.B, PROF.n_limbs), dtype=np.int32)
        for pi, (pid, shares) in enumerate(zip(party_ids, party_shares)):
            lam = hm.lagrange_coeff(quorum_xs, universe_xs[pid], hm.ED_L)
            self.lamx[pi] = scalars_to_limb_batch(
                [lam * s.share % hm.ED_L for s in shares]
            )
            for s in shares:
                if s.key_type != "ed25519":
                    raise ValueError("wrong key type")
                if s.participants != first.participants:
                    raise ValueError(
                        f"share for {pid!r} from a different participant "
                        f"universe — bucket sessions by topology"
                    )
                if s.threshold != first.threshold:
                    raise ValueError("mixed thresholds in one batch")
                if s.self_x != universe_xs[pid]:
                    raise ValueError(
                        f"share self_x {s.self_x} does not belong to "
                        f"{pid!r} (expected {universe_xs[pid]}) — "
                        f"party_shares misaligned with party_ids"
                    )
        self.A_comp = np.stack(
            [
                np.frombuffer(s.public_key, dtype=np.uint8)
                for s in party_shares[0]
            ]
        )
        self._A_dev = jnp.asarray(self.A_comp)  # uploaded once, reused every batch

    def sign(
        self, messages: Sequence[bytes], cohorts: Optional[int] = None
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Run the full 3-round protocol for B sessions → ((B, 64)
        signatures, (B,) ok mask). Raises on commitment fraud.

        The batch executes as K counter-phase cohorts (engine/pipeline;
        ``cohorts=`` overrides MPCIUM_PIPELINE_COHORTS): each cohort's
        donated round steps dispatch asynchronously while another
        cohort's host stage (fraud verdict, signature egress) drains on
        the pipeline worker. ALL nonce/blind bytes are drawn for the
        full batch, in K=1 serial order, before the split — signatures
        are bit-identical for every K (tests/test_pipeline.py). Messages
        of different lengths take the same path: their lengths are data
        to the challenge hash (:func:`pack_messages`). The host-hash
        paths (MPCIUM_EDDSA_DEVICE_HASH=0, a message past the top rung)
        stay serial.

        With mpctrace armed, device-phase spans (``phase:*``) are emitted
        with a sync at each phase boundary; untraced runs take the no-op
        path — no syncs, bit-identical results."""
        assert len(messages) == self.B
        q, B = self.q, self.B
        # mpcshape: unbounded-ok — B is pow-2 snapped upstream (scheduler chunks via engine/buckets.floor_bucket; bench via bucket_b)
        _cw = compile_watch.begin("eddsa.sign", f"B{B}|q{q}")

        # ALL secret randomness precedes the cohort split (transcript
        # discipline: the rng stream is identical for every K)
        r64 = np.stack([fresh_nonce_bytes(B, self.rng) for _ in range(q)])
        blinds = np.stack([
            np.frombuffer(self.rng.token_bytes(B * 32), dtype=np.uint8)
            .reshape(B, 32) for _ in range(q)
        ])

        use_dev_hash = device_hash_enabled()
        packed = pack_messages(messages) if use_dev_hash else None
        if packed is None:
            out = self._sign_fallback(messages, r64, blinds, use_dev_hash)
            compile_watch.finish(_cw)
            return out

        from . import pipeline as pl

        plan = pl.CohortPlan.for_batch(B, cohorts)
        Mrows, Mlens = packed
        pref = jnp.asarray(
            np.frombuffer(b"mpcium-tpu/eddsa-commit", np.uint8)
        )

        def job(ci: int, sl: slice):
            def run():
                _pt = tracing.PhaseTimer(
                    "eddsa.sign", _trace_sync, node="engine",
                    tid=f"eddsa:B{B}" if plan.serial
                    else f"eddsa:B{B}:c{ci}",
                )
                # donated round-step chain: st stays on device with its
                # ingress sharding; rebind-only (MPS906)
                st = {
                    "r64": to_dev(r64[:, sl], axis=1),
                    "blinds": to_dev(blinds[:, sl], axis=1),
                }
                st = round_step_nonce(st, pref)
                _pt.mark("r1_nonce_commit", st["commits"])
                st = round_step_aggregate(st)
                _pt.mark("r2_decommit_aggregate", st["R_sum"])
                fraud_free = yield (
                    "fraud_verdict",
                    lambda: bool(np.asarray(st["fraud_free"])),  # mpcflow: host-ok — commitment-fraud verdict egress (one bool)
                )
                if not fraud_free:
                    raise RuntimeError("commitment fraud detected")
                A_c = self._A_dev[sl]
                c64 = challenge_device(
                    st["R_sum"], A_c, to_dev(Mrows[sl]), Mlens[sl]
                )
                st = round_step_partial(
                    st, c64, to_dev(self.lamx[:, sl], axis=1)
                )
                _pt.mark("r3_challenge_partials_combine", st["sigs"])
                # local verification before publishing (reference
                # eddsa_signing_session.go:147)
                ok = verify_signatures(st["sigs"], A_c, c64) & st["ok_R"]
                _pt.mark("verify", ok)
                sigs = st["sigs"]
                out = yield (
                    "sig_egress",
                    lambda: (np.asarray(sigs), np.asarray(ok)),  # mpcflow: host-ok — signature egress: final (R,s) + verdicts leave device for callers
                )
                return out

            return run

        parts = pl.run_counter_phase(
            [job(ci, sl) for ci, sl in enumerate(plan.slices())]
        )
        out = (
            pl.merge_rows([p[0] for p in parts]),
            pl.merge_rows([p[1] for p in parts]),
        )
        compile_watch.finish(_cw)
        return out

    def _sign_fallback(
        self,
        messages: Sequence[bytes],
        r64: np.ndarray,
        blinds: np.ndarray,
        use_dev_hash: bool,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """The serial (K=1) path for the host challenge hash:
        MPCIUM_EDDSA_DEVICE_HASH=0, and a message past the top rung
        (``use_dev_hash`` then still holds for the commitments). Same
        rounds, no cohort split."""
        q, B = self.q, self.B
        _pt = tracing.PhaseTimer(
            "eddsa.sign", _trace_sync, node="engine", tid=f"eddsa:B{B}",
        )

        # -- round 1: nonce commitments (one (q, B) dispatch) + batch
        # commitments (device SHA-256 over the (q, B) rows where R
        # already lives; MPCIUM_EDDSA_DEVICE_HASH=0 restores the native
        # C++ per-party calls) ------------------------------------------------
        from .. import native

        r_limbs, R_comp = nonce_commitments(jnp.asarray(r64))  # (q,B,22)/(q,B,32)
        if use_dev_hash:
            pref = jnp.asarray(
                np.frombuffer(b"mpcium-tpu/eddsa-commit", np.uint8)
            )
            commit_msg = jnp.concatenate(
                [
                    jnp.broadcast_to(pref, (q, B) + pref.shape),
                    jnp.asarray(blinds),
                    R_comp,
                ],
                axis=-1,
            )
            commits = hs.sha256(commit_msg)
        else:
            R_host = np.asarray(R_comp)  # mpcflow: host-ok — MPCIUM_EDDSA_DEVICE_HASH=0 fallback: native hasher reads host rows; the default device path keeps R on device
            commits = [
                native.batch_sha256(
                    b"mpcium-tpu/eddsa-commit",
                    np.concatenate([blinds[p], R_host[p]], axis=1),
                )
                for p in range(q)
            ]
        _pt.mark("r1_nonce_commit", commits)

        # -- round 2: decommit + verify (re-hash the received tensors,
        # one fraud verdict; device aggregate) --------------------------------
        if use_dev_hash:
            again = hs.sha256(commit_msg)
            fraud_free = np.asarray(jnp.all(again == commits))  # mpcflow: host-ok — commitment-fraud verdict egress (one bool)
            if not fraud_free:
                raise RuntimeError("commitment fraud detected")
            R_sum, ok_R = aggregate_nonce(R_comp)
        else:
            for p in range(q):
                again = native.batch_sha256(
                    b"mpcium-tpu/eddsa-commit",
                    np.concatenate([blinds[p], R_host[p]], axis=1),
                )
                if not (again == commits[p]).all():
                    raise RuntimeError("commitment fraud detected")
            R_sum, ok_R = aggregate_nonce(jnp.asarray(R_host))
        _pt.mark("r2_decommit_aggregate", R_sum)

        # -- round 3: challenge on the host (this path is the host
        # hash's) + partials (one (q, B) dispatch)
        c64 = jnp.asarray(
            challenge_hashes(
                np.asarray(R_sum), self.A_comp, messages  # mpcflow: host-ok — host challenge hash (MPCIUM_EDDSA_DEVICE_HASH=0, or a message past the top rung); sign() keeps R on device
            )
        )
        parts = partial_signature(
            r_limbs,
            jnp.broadcast_to(c64, (q,) + c64.shape),
            jnp.asarray(self.lamx),
        )
        sigs, _ = combine_signatures(parts, R_sum)
        _pt.mark("r3_challenge_partials_combine", sigs)

        # -- local verification before publishing (reference
        # eddsa_signing_session.go:147) --------------------------------------
        ok = verify_signatures(sigs, self._A_dev, c64)
        _pt.mark("verify", ok)
        return (
            np.asarray(sigs),  # mpcflow: host-ok — signature egress: final (R,s) leave device for callers
            np.asarray(ok & ok_R),  # mpcflow: host-ok — per-wallet verification verdicts, egress with the signatures
        )


def dealer_keygen_batch(
    n_wallets: int,
    party_ids: Sequence[str],
    threshold: int,
    rng=secrets,
):
    """Trusted-dealer batch keygen for tests/bench setup ONLY — production
    wallets come from the DKG protocol (protocol.eddsa.keygen). Returns
    per-party lists of KeygenShare: result[i] belongs to party_ids[i],
    wallet order aligned across parties."""
    from ..protocol.base import KeygenShare, party_xs

    xs = party_xs(party_ids)
    out = [[] for _ in party_ids]
    for _ in range(n_wallets):
        secret = rng.randbelow(hm.ED_L - 1) + 1
        _, shares = hm.shamir_share(
            secret, threshold, [xs[p] for p in party_ids], hm.ED_L, rng=rng
        )
        pub = hm.ed_compress(hm.ed_mul(secret, hm.ED_B))
        for i, pid in enumerate(party_ids):
            out[i].append(
                KeygenShare(
                    key_type="ed25519",
                    share=shares[xs[pid]],
                    self_x=xs[pid],
                    public_key=pub,
                    participants=sorted(party_ids),
                    threshold=threshold,
                )
            )
    return out
