"""MXU-formulated batched modular arithmetic (the hot path of GG18).

The generic engine in :mod:`core.bignum` expresses everything as int32
einsums and sequential carry scans — correct, but it leaves the MXU idle
and serializes on limb-length scans. This module re-formulates the same
operations around three measured-on-chip facts (TPU v5e, B=4096, 4096-bit
operands; measurements from the on-chip microbenches):

1. **Multiplication by a per-modulus constant is a Toeplitz matmul.**
   Barrett reduction multiplies by two constants (mu and m). With 7-bit
   limbs both operands are exact in bf16 and every f32 partial sum stays
   below 2^24, so ``x @ Toeplitz(c)`` runs on the MXU at full bf16 speed
   with bit-exact integer results (~0.04 ms vs 0.33 ms for the int32
   einsum product).
2. **Carry propagation does not need an O(n) scan.** Three shift-and-add
   roll passes bound every limb by 135, after which carries are 0/1 and a
   logarithmic carry-lookahead (``lax.associative_scan`` over the classic
   generate/propagate semiring) finishes exact normalization.
3. **Conditional subtraction needs no lexicographic compare.** Adding the
   radix-complement constant R^k - m and inspecting the top carry limb
   gives the borrow bit and the difference in one carry pass.

Pairwise (batched x batched) products keep the blocked-einsum form of
``bignum.mul_wide`` but in the 7-bit limb family, which measured 3.8x
faster than the 11-bit family (0.088 ms vs 0.333 ms at B=4096) — XLA maps
the small-block einsum far better at 32-aligned widths with small values.

Reference correspondence: this is the execution engine for the tss-lib
Paillier/MtA arithmetic (SURVEY.md §2.3; reference delegates to
bnb-chain/tss-lib — pkg/mpc/ecdsa_signing_session.go drives it one session
at a time). Here the leading axis is the concurrent-session batch.

Representation: little-endian int32 limb tensors, 7 bits per limb
(radix 128), shape (..., n_limbs) — normalized unless stated otherwise.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core import bignum as bn
from ..utils import log


def _jit_method(fn=None, *, static_argnums=(0,)):
    """jit with `self` static (instances hash by identity; each context
    owns its jit cache). Keeps the per-modulus Toeplitz/comb constants out
    of call signatures — they embed as compile-time constants."""
    if fn is None:
        return lambda f: jax.jit(f, static_argnums=static_argnums)
    return jax.jit(fn, static_argnums=static_argnums)

LIMB_BITS = 7
RADIX = 1 << LIMB_BITS
MASK = RADIX - 1

# blocked pairwise product: 32-limb blocks (same shape bignum.mul_wide uses)
_BLOCK = 32


def profile(value_bits: int) -> bn.LimbProfile:
    """7-bit limb profile sized for ``value_bits``, block-aligned."""
    n = -(-value_bits // LIMB_BITS)
    n = -(-n // _BLOCK) * _BLOCK  # pad to block multiple: einsum + matmul tile
    return bn.LimbProfile(bits=LIMB_BITS, n_limbs=n)


# ---------------------------------------------------------------------------
# carries: roll passes + logarithmic carry-lookahead
# ---------------------------------------------------------------------------


def _roll_pass(x: jnp.ndarray) -> jnp.ndarray:
    """One shift-and-add carry pass (keeps the value, shrinks the limbs)."""
    hi = x >> LIMB_BITS
    lo = x & MASK
    return lo + jnp.pad(hi, [(0, 0)] * (x.ndim - 1) + [(1, 0)])[..., :-1]


def carry(x: jnp.ndarray) -> jnp.ndarray:
    """Exact normalization of non-negative redundant limbs (total value
    must fit the limb count; same contract as bignum.carry minus
    negative-limb support).

    Exactness bound: each roll pass maps a limb bound M to 127 + M/128,
    so after three passes limbs are ≤ 127 + M/2²¹ + ~1; the lookahead
    stage needs limbs ≤ 255 (carries 0/1), giving the input contract
    **limb < 127·2²¹ ≈ 2^27.99**. Callers on the narrow paths stay below
    2²⁴; the i8 wide fallback approaches the true bound and is guarded
    at its call site.
    """
    x = _roll_pass(_roll_pass(_roll_pass(x)))
    # now 0 <= limb <= 135: incoming carries are 0/1
    g = (x >> LIMB_BITS).astype(jnp.int32)  # generate: 0/1
    r = x & MASK
    p = (r == MASK).astype(jnp.int32)  # propagate

    def op(a, b):
        ga, pa = a
        gb, pb = b
        return gb | (pb & ga), pb & pa

    G, _ = lax.associative_scan(op, (g, p), axis=-1)
    cin = jnp.pad(G, [(0, 0)] * (x.ndim - 1) + [(1, 0)])[..., :-1]
    return (r + cin) & MASK


# ---------------------------------------------------------------------------
# products
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _toeplitz_np(c_limbs: Tuple[int, ...], n_in: int) -> np.ndarray:
    """(n_in, n_in + len(c) - 1) f32 band matrix T[i, i+j] = c[j]."""
    m = len(c_limbs)
    T = np.zeros((n_in, n_in + m - 1), dtype=np.float32)
    for j, cj in enumerate(c_limbs):
        if cj:
            T[np.arange(n_in), np.arange(n_in) + j] = float(cj)
    return T


def _const_matrices(
    value: int, n_in: int, min_limbs: int = 1
) -> jnp.ndarray:
    limbs = []
    v = value
    while v:
        limbs.append(v & MASK)
        v >>= LIMB_BITS
    while len(limbs) < min_limbs:
        limbs.append(0)  # width-pad so same-modulus constants share shapes
    return jnp.asarray(_toeplitz_np(tuple(limbs), n_in), jnp.bfloat16)


def ints_to_limbs(vals, prof: bn.LimbProfile) -> np.ndarray:
    """Bulk python-int → limb conversion via byte packing (numpy-speed;
    bn.to_limbs is a per-limb python loop). A limb of up to 17 bits lies
    within three consecutive bytes: it is read out of that 24-bit window
    (no per-bit temporaries)."""
    assert prof.bits <= 17
    nbytes = -(-prof.bits * prof.n_limbs // 8)
    raw = np.frombuffer(
        b"".join(int(v).to_bytes(nbytes + 2, "little") for v in vals),
        dtype=np.uint8,
    ).reshape(len(vals), nbytes + 2)
    at = prof.bits * np.arange(prof.n_limbs)
    byte, shift = at // 8, (at % 8).astype(np.uint32)
    window = (
        raw[:, byte].astype(np.uint32)
        | raw[:, byte + 1].astype(np.uint32) << 8
        | raw[:, byte + 2].astype(np.uint32) << 16
    )
    return ((window >> shift) & ((1 << prof.bits) - 1)).astype(np.int32)


def mul_const(x: jnp.ndarray, T: jnp.ndarray) -> jnp.ndarray:
    """x (normalized limbs) times a constant via its Toeplitz matrix →
    UNNORMALIZED int32 columns (each < n_in·127² < 2^24; caller carries).

    Exact: 7-bit limbs are exact bf16 values, partial products ≤ 127²
    are exact, and f32 accumulation stays integral below 2^24 (requires
    n_in ≤ 1040 limbs ⇒ moduli up to ~7280 bits).
    """
    assert x.shape[-1] == T.shape[0] and x.shape[-1] <= 1040
    out = lax.dot_general(
        x.astype(jnp.bfloat16),
        T,
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return out.astype(jnp.int32)


import os

# Pairwise-product strategy: "bf16" (default — blocked einsum with bf16
# multiplicands and f32 accumulation, exact for 7-bit limbs, rides the
# MXU's native bf16 path) or "i32" (the round-3 blocked int32 einsum,
# kept as an escape hatch / differential-test oracle via MPCIUM_MULPAIR).
MULPAIR_STRATEGY = os.environ.get("MPCIUM_MULPAIR", "bf16")

# lax.scan body unrolling for exponentiation windows: each step is ~5
# mulmods (4 squarings + 1 table multiply); unrolling amortizes the TPU
# while-loop per-step overhead (PERFORMANCE.md gap 3) at the price of a
# proportionally larger compile. Default stays 1: on this 1-core host
# compile time is the scarcer resource than scan-step overhead (it ate
# two bench windows already, PERFORMANCE.md); flip via MPCIUM_SCAN_UNROLL
# once the on-chip microbench (.scratch/chipcheck.py) proves the win.
SCAN_UNROLL = int(os.environ.get("MPCIUM_SCAN_UNROLL", "1"))

# Fixed-base comb window width (bits). Combs have no squarings, so the
# mulmod count scales 1/w while table size scales 2^w/w; 8 halves the
# wide-exponent ring-Pedersen legs vs 4. Per-element-base powmods keep
# 4-bit windows (squarings dominate there; wider windows barely help).
COMB_W = int(os.environ.get("MPCIUM_COMB_W", "8"))

# Dispatch audit: set to a dict to accumulate mulmod-equivalent counts
# per (op, modulus-bits); None disables (no overhead on the hot path).
AUDIT = None

# Cumulative device-resident comb/constant table bytes across ALL contexts
# in this process. COMB_W=8 costs ~16x the table memory of w=4 (~100 MB
# per (base, 2048-bit modulus) comb, ~200 MB per counterparty NTilde), so
# larger committees can pressure HBM with nothing attributing it; each
# build is logged and crossing the soft cap warns once per GB.
_FB_TABLE_BYTES = 0
_FB_TABLE_WARN_GB = float(os.environ.get("MPCIUM_FB_TABLE_WARN_GB", "4"))


def _track_fb_table(nbytes: int, what: str, mod_bits: int) -> None:
    global _FB_TABLE_BYTES
    prev_gb = _FB_TABLE_BYTES / (1 << 30)
    _FB_TABLE_BYTES += nbytes
    now_gb = _FB_TABLE_BYTES / (1 << 30)
    log.debug(
        "fixed-base table built", kind=what, mod_bits=mod_bits,
        table_mb=round(nbytes / (1 << 20), 1),
        cumulative_mb=round(_FB_TABLE_BYTES / (1 << 20), 1),
    )
    if _FB_TABLE_WARN_GB > 0 and (
        int(now_gb / _FB_TABLE_WARN_GB) > int(prev_gb / _FB_TABLE_WARN_GB)
    ):
        log.warn(
            "cumulative fixed-base table memory crossed soft cap — "
            "HBM pressure is likely attributable to comb tables; "
            "lower MPCIUM_COMB_W or raise MPCIUM_FB_TABLE_WARN_GB",
            cumulative_gb=round(now_gb, 2), soft_cap_gb=_FB_TABLE_WARN_GB,
        )

# Largest block count for which the bf16 overlap-add stays f32-exact:
# each 32-limb block-product column is ≤ 32·127² = 516,128 and the
# overlap-add at any output block sums ≤ min(bx, by) columns, so
# min(bx, by) ≤ 32 keeps every partial sum ≤ 16,516,096 < 2²⁴.
_BF16_MAX_BLOCKS = 32

# The i8 strategy's int32 overlap-add is exact at any width, but the
# final carry() bounds it: lo+hi limbs reach 2*min(bx,by)*32*127^2,
# which must stay below carry()'s 127*2^21 limit => min(bx,by) <= 258;
# 256 keeps a margin (operands up to ~57k bits).
_I8_MAX_BLOCKS = 256


@functools.lru_cache(maxsize=None)
def _band_index_mask(n_cols: int):
    """Gather indices + mask building the Toeplitz band of a 32-limb block:
    band[i, n] = block[n - i] for 0 <= n-i < _BLOCK else 0. Cached as
    NUMPY (device conversion happens per trace: jnp.asarray under a jit
    trace yields a tracer, and caching tracers across traces leaks)."""
    i = np.arange(_BLOCK)[:, None]
    nn = np.arange(n_cols)[None, :]
    d = nn - i
    ok = (d >= 0) & (d < _BLOCK)
    return (
        np.clip(d, 0, _BLOCK - 1).astype(np.int32),
        ok.astype(np.float32),
    )


def _mul_pair_band(
    x: jnp.ndarray, y: jnp.ndarray, op_dtype
) -> jnp.ndarray:
    """Band-matrix pairwise product on the MXU, shared by the bf16 and
    int8 strategies (``op_dtype`` picks the operand path).

    Stage 1 builds the Toeplitz band of each 32-limb block of y
    (band[v, i, n] = y_v[n-i]) and contracts the limb index on the MXU:
    prods[..., u, v, n] = Σ_i x_u[i]·y_v[n-i] — a clean batched GEMM
    instead of the 3-operand conv einsum (whose outer-product
    materialization was ~25× slower than equivalent-MAC matmuls on the
    chip). Accumulation: bf16 operands accumulate in f32 (exact — 7-bit
    limbs are exact bf16 values and block columns stay ≤ 32·127² < 2²⁴);
    int8 operands accumulate in int32 (exact at every width).

    Stage 2 (overlap-add) sums ≤ min(bx, by) block columns; while
    min(bx, by) ≤ 32 every partial sum stays < 2²⁴ and it runs as an
    f32×f32 matmul at Precision.HIGHEST, which is f32-faithful on the
    TPU MXU (DEFAULT precision demotes f32 dots to one bf16 pass and
    silently rounds — the round-4 on-chip correctness lesson). Past 32
    blocks the int8 path falls back to an exact int32 contraction
    (stage 1 stays exact for BOTH dtypes at any width — the K=32 band
    contraction's sums never exceed 32·127²; only the f32 overlap-add
    breaks — but giving bf16 the int32 fallback too would silently
    change its cost profile, so it rejects instead). The fallback's own
    ceiling is the final carry: lo+hi limbs reach 2·min(bx,by)·32·127²,
    which must stay under carry()'s 127·2²¹ bound ⇒ min(bx, by) ≤ 256
    (operands ≤ ~57k bits), guarded below.
    Requires NORMALIZED inputs (the i32 strategy tolerates mildly
    redundant limbs; this one does not).
    """
    n_x, n_y = x.shape[-1], y.shape[-1]
    bx, by = -(-n_x // _BLOCK), -(-n_y // _BLOCK)
    wide = min(bx, by) > _BF16_MAX_BLOCKS
    # hard errors, not asserts: these guard cryptographic correctness
    # and must survive `python -O`
    if wide and op_dtype == jnp.bfloat16:
        raise ValueError(
            f"bf16 pairwise product overlap-add would exceed 2^24 "
            f"exactness: min({bx}, {by}) blocks > {_BF16_MAX_BLOCKS} "
            f"(operands up to {_BF16_MAX_BLOCKS * _BLOCK * LIMB_BITS} "
            f"bits); use MPCIUM_MULPAIR=i8 or i32 for wider operands"
        )
    if min(bx, by) > _I8_MAX_BLOCKS:
        raise ValueError(
            f"i8 pairwise product would exceed the carry-normalization "
            f"bound (limbs ≥ 127·2^21): min({bx}, {by}) blocks > "
            f"{_I8_MAX_BLOCKS}; use MPCIUM_MULPAIR=i32 for wider operands"
        )
    acc_dtype = jnp.float32 if op_dtype == jnp.bfloat16 else jnp.int32
    xb = bn.take_limbs(x, 0, bx * _BLOCK).reshape(
        x.shape[:-1] + (bx, _BLOCK)
    ).astype(op_dtype)
    yb = bn.take_limbs(y, 0, by * _BLOCK).reshape(
        y.shape[:-1] + (by, _BLOCK)
    ).astype(op_dtype)
    idx, mask = _band_index_mask(2 * _BLOCK - 1)
    # band[..., v, i, n] = y_v[n - i] (0 outside the band)
    band = jnp.take(yb, jnp.asarray(idx), axis=-1) * jnp.asarray(
        mask, op_dtype
    )
    prods = jnp.einsum(
        "...ui,...vin->...uvn", xb, band,
        preferred_element_type=acc_dtype,
    )
    bt = bx + by - 1
    if wide:
        # exact int32 overlap-add (VPU; only reachable from the i8 path)
        prods = prods.astype(jnp.int32)
        blk = jnp.asarray(np.asarray(bn._conv_tensor(bx, by)), jnp.int32)
        lo = jnp.einsum("...uvn,uvt->...tn", prods[..., :_BLOCK], blk)
        hi = jnp.einsum("...uvn,uvt->...tn", prods[..., _BLOCK:], blk)
    else:
        prods = prods.astype(jnp.float32)
        blk = jnp.asarray(np.asarray(bn._conv_tensor(bx, by)), jnp.float32)
        lo = jnp.einsum(
            "...uvn,uvt->...tn", prods[..., :_BLOCK], blk,
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)
        hi = jnp.einsum(
            "...uvn,uvt->...tn", prods[..., _BLOCK:], blk,
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)
    hi = jnp.pad(hi, [(0, 0)] * (hi.ndim - 1) + [(0, 1)])
    lo_flat = jnp.pad(
        lo.reshape(lo.shape[:-2] + (bt * _BLOCK,)),
        [(0, 0)] * (lo.ndim - 2) + [(0, _BLOCK)],
    )
    hi_flat = jnp.pad(
        hi.reshape(hi.shape[:-2] + (bt * _BLOCK,)),
        [(0, 0)] * (hi.ndim - 2) + [(_BLOCK, 0)],
    )
    total = carry(lo_flat + hi_flat)
    return total[..., : n_x + n_y]


def _mul_pair_bf16(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    return _mul_pair_band(x, y, jnp.bfloat16)


def _mul_pair_i8(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """int8 band strategy: half the band traffic of bf16, int32
    accumulation — exact up to 256-block operands (~57k bits; past the
    32-block f32 bound the overlap-add falls back to int32, and the
    carry-normalization bound caps the fallback — see _mul_pair_band).
    Whether XLA maps the batched K=32 contraction onto the int8 MXU path
    is measured on the real chip by .scratch/chipcheck.py."""
    return _mul_pair_band(x, y, jnp.int8)


def mul_pair(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Pairwise (batched × batched) product → normalized (n_x+n_y) limbs.
    Blocked einsum in the 7-bit family; strategy via MPCIUM_MULPAIR
    (bf16 | i8 | i32)."""
    if MULPAIR_STRATEGY == "bf16":
        return _mul_pair_bf16(x, y)
    if MULPAIR_STRATEGY == "i8":
        return _mul_pair_i8(x, y)
    prof = bn.LimbProfile(bits=LIMB_BITS, n_limbs=max(x.shape[-1], y.shape[-1]))
    return bn.mul_wide(x, y, prof)


# ---------------------------------------------------------------------------
# module-level kernels (operand-passing: per-modulus constants arrive as
# ARGUMENTS, so one compiled executable serves every modulus of a given
# width — across parties, keys, processes, and the persistent cache)
# ---------------------------------------------------------------------------


def _cond_sub_impl(x: jnp.ndarray, comp: jnp.ndarray, occ: int) -> jnp.ndarray:
    """x < 2m over occ+1 limbs -> x mod m (complement-add carry)."""
    c = jnp.broadcast_to(comp, x.shape[:-1] + (occ + 2,))
    u = carry(bn.pad_limbs(x, 1) + c)  # x - m + R^(occ+1)
    ge = u[..., occ + 1] >= 1  # borrow-free <=> x >= m
    return jnp.where(ge[..., None], u[..., : occ + 1], x)


def _reduce_impl(x, T_mu, T_m, comp, occ: int, n: int) -> jnp.ndarray:
    """Barrett reduce; x normalized <= 2n limbs, x < R^occ * m (any product
    of two reduced values qualifies) -> x mod m over n limbs."""
    if x.shape[-1] <= occ:
        x = bn.pad_limbs(x, occ + 2 - x.shape[-1])
    q1 = bn.take_limbs(x, occ - 1, x.shape[-1] - (occ - 1))
    q2 = carry(mul_const(q1, T_mu[: q1.shape[-1]]))
    q3 = bn.take_limbs(q2, occ + 1, q2.shape[-1] - (occ + 1))
    q3m = carry(mul_const(q3, T_m[: q3.shape[-1]]))
    # subtract via elementwise radix complement of q3m (keeps limbs
    # non-negative for the lookahead carry); true r in [0, 3m) so the
    # extra R^(occ+1) lands exactly in limb occ+1, dropped below
    t = bn.take_limbs(x, 0, occ + 1) + (MASK - bn.take_limbs(q3m, 0, occ + 1))
    t = bn.pad_limbs(t, 1).at[..., 0].add(1)
    r = carry(t)[..., : occ + 1]
    r = _cond_sub_impl(r, comp, occ)
    r = _cond_sub_impl(r, comp, occ)
    out = r[..., :occ]
    return bn.pad_limbs(out, n - occ) if occ < n else out


def _one_like(x: jnp.ndarray, n: int) -> jnp.ndarray:
    return jnp.zeros(x.shape[:-1] + (n,), jnp.int32).at[..., 0].set(1)


@functools.partial(jax.jit, static_argnames=("occ", "n"))
def _k_reduce(x, T_mu, T_m, comp, occ: int, n: int):
    return _reduce_impl(x, T_mu, T_m, comp, occ, n)


# Pairwise-mulmod implementation: "band" = Toeplitz-band GEMM + XLA-fused
# Barrett (the round-4 default); "pallas" = the fully fused VMEM-resident
# kernel in ops.pallas_mulmod (conv + carries + Barrett legs in ONE
# pallas_call — no HBM round-trips between stages). Uniform across every
# powmod/mulmod kernel in a process; unset, the choice follows the
# backend — pallas on real TPU (measured on-chip: 6.4x at 2048-bit,
# 1.35x at 4096-bit, flagship 13.7 vs 8.9 sigs/s), band on CPU (where
# pallas would run interpreted, orders of magnitude slower).
MULMOD_IMPL = os.environ.get("MPCIUM_MULMOD", "")
if MULMOD_IMPL not in ("", "band", "pallas"):
    raise ValueError(
        f"MPCIUM_MULMOD={MULMOD_IMPL!r}: expected 'band' or 'pallas'"
    )


def _impl() -> str:
    """Resolve the implementation at first-trace time (the backend is
    not known at import time; jax.default_backend() initializes it)."""
    global MULMOD_IMPL
    if not MULMOD_IMPL:
        MULMOD_IMPL = (
            "pallas" if jax.default_backend() == "tpu" else "band"
        )
    return MULMOD_IMPL


def _mm(a, b, T_mu, T_m, comp, occ: int, n: int) -> jnp.ndarray:
    """a·b mod m — the one mul+reduce step every kernel below loops."""
    if _impl() == "pallas":
        from . import pallas_mulmod

        return pallas_mulmod.mulmod(
            a, b, T_mu, T_m, comp, occ, n,
            interpret=jax.default_backend() == "cpu",
        )
    return _reduce_impl(mul_pair(a, b), T_mu, T_m, comp, occ, n)


@functools.partial(jax.jit, static_argnames=("occ", "n"))
def _k_mulmod(a, b, T_mu, T_m, comp, occ: int, n: int):
    return _mm(a, b, T_mu, T_m, comp, occ, n)


@functools.partial(jax.jit, static_argnames=("occ", "n"))
def _k_mulmod_const(a, T_c, T_mu, T_m, comp, occ: int, n: int):
    return _reduce_impl(carry(mul_const(a, T_c)), T_mu, T_m, comp, occ, n)


@functools.partial(jax.jit, static_argnames=("occ", "n"))
def _k_addmod(a, b, comp, occ: int, n: int):
    s = carry(bn.pad_limbs(a + b, 1))  # < 2m
    r = _cond_sub_impl(bn.take_limbs(s, 0, occ + 1), comp, occ)
    out = r[..., :occ]
    return bn.pad_limbs(out, n - occ) if occ < n else out


@functools.partial(jax.jit, static_argnames=("occ", "n"))
def _k_submod(a, b, m1, comp, occ: int, n: int):
    # a - b + m via the elementwise complement of b (non-negative limbs)
    t = (
        bn.take_limbs(a, 0, occ + 1)
        + (MASK - bn.take_limbs(b, 0, occ + 1))
        + bn.pad_limbs(m1, 1)[..., : occ + 1]
    )
    t = bn.pad_limbs(t, 1).at[..., 0].add(1)
    r = carry(t)[..., : occ + 1]  # a - b + m in (0, 2m); drop R^(occ+1)
    r = _cond_sub_impl(r, comp, occ)
    out = r[..., :occ]
    return bn.pad_limbs(out, n - occ) if occ < n else out


@functools.partial(jax.jit, static_argnames=("occ", "n"))
def _k_powmod(x, ebits, T_mu, T_m, comp, occ: int, n: int):
    """x^e, per-element exponent bits (LSB-first), 4-bit windows."""
    n_bits = ebits.shape[-1]
    nw = -(-n_bits // 4)
    if nw * 4 != n_bits:
        ebits = jnp.pad(
            ebits, [(0, 0)] * (ebits.ndim - 1) + [(0, nw * 4 - n_bits)]
        )
    w = ebits.reshape(ebits.shape[:-1] + (nw, 4))
    digits = jnp.flip(
        (w * jnp.asarray([1, 2, 4, 8], jnp.int32)).sum(-1), axis=-1
    )
    rows = [_one_like(x, n), x]
    for _ in range(14):
        rows.append(_mm(rows[-1], x, T_mu, T_m, comp, occ, n))
    tbl = jnp.stack(rows, axis=-2)

    def step(acc, d):
        for _ in range(4):
            acc = _mm(acc, acc, T_mu, T_m, comp, occ, n)
        sel = jnp.take_along_axis(
            tbl, d[..., None, None].astype(jnp.int32), axis=-2
        )[..., 0, :]
        return _mm(acc, sel, T_mu, T_m, comp, occ, n), None

    acc, _ = lax.scan(step, _one_like(x, n), jnp.moveaxis(digits, -1, 0),
                      unroll=SCAN_UNROLL)
    return acc


@functools.partial(jax.jit, static_argnames=("occ", "n"))
def _k_powmod_digits(x, digits, T_mu, T_m, comp, occ: int, n: int):
    """x^e for a batch-shared exponent given as an MSD-first (nw,) digit
    array (value is a runtime operand: one compile per digit COUNT)."""
    rows = [_one_like(x, n), x]
    for _ in range(14):
        rows.append(_mm(rows[-1], x, T_mu, T_m, comp, occ, n))
    tbl = jnp.stack(rows, axis=-2)

    def step(acc, d):
        for _ in range(4):
            acc = _mm(acc, acc, T_mu, T_m, comp, occ, n)
        sel = tbl[..., d, :]
        return _mm(acc, sel, T_mu, T_m, comp, occ, n), None

    acc, _ = lax.scan(step, _one_like(x, n), digits, unroll=SCAN_UNROLL)
    return acc


@functools.partial(jax.jit, static_argnames=("occ", "n"))
def _k_powmod_fb(tbl, ebits, T_mu, T_m, comp, occ: int, n: int):
    """comb-table fixed-base: tbl (nw, 2^w, n) operand, one mulmod per
    w-bit window (no squarings — fixed-base combs scale 1/w with window
    width, unlike per-element-base exponentiation whose squarings
    dominate; the window width is derived from the table shape)."""
    n_bits = ebits.shape[-1]
    nw = tbl.shape[0]
    wbits = tbl.shape[1].bit_length() - 1  # 2^w rows per window
    if nw * wbits != n_bits:
        ebits = jnp.pad(
            ebits, [(0, 0)] * (ebits.ndim - 1) + [(0, nw * wbits - n_bits)]
        )
    w = ebits.reshape(ebits.shape[:-1] + (nw, wbits))
    digits = (w * jnp.asarray([1 << i for i in range(wbits)], jnp.int32)).sum(-1)

    def step(acc, sl):
        d, rows = sl
        sel = rows[d]
        return _mm(acc, sel, T_mu, T_m, comp, occ, n), None

    acc, _ = lax.scan(
        step, _one_like(ebits, n), (jnp.moveaxis(digits, -1, 0), tbl),
        unroll=SCAN_UNROLL,
    )
    return acc


# Windows a comb-build program takes at once (its lane count): a table's
# windows go through in chunks of the smallest of these that holds them
# all, else of the largest, so one executable a modulus width builds every
# large table whatever its window count, and a small table (a test's, a
# short exponent's) does not pay for 128 lanes.
_COMB_LANES = (8, 32, 128)


@functools.partial(jax.jit, static_argnames=("occ", "n"))
def _k_comb_rows(bases, T_mu, T_m, comp, occ: int, n: int):
    """The rows of comb windows: bases (L, n) canonical residues →
    (L, 2^COMB_W, n) with row w = bases^w mod m, each from the one before
    it, the L windows as the lanes of one mulmod a step."""

    def step(acc, _):
        return _mm(acc, bases, T_mu, T_m, comp, occ, n), acc

    _, rows = lax.scan(step, _one_like(bases, n), None, length=1 << COMB_W)
    return jnp.moveaxis(rows, 0, 1)


@functools.partial(jax.jit, static_argnames=("nw",))
def _k_comb_take(chunks, nw: int):
    """The first ``nw`` windows of a table built in chunks."""
    return jnp.concatenate(chunks)[:nw]


# ---------------------------------------------------------------------------
# the modular context
# ---------------------------------------------------------------------------


def _exp_digits(exponent: int) -> jnp.ndarray:
    """4-bit digits of a constant exponent, most significant first."""
    nw = -(-exponent.bit_length() // 4)
    return jnp.asarray(
        [(exponent >> (4 * i)) & 15 for i in range(nw)][::-1], jnp.int32
    )


class MXUBarrett:
    """Barrett context for a fixed modulus with MXU-formulated primitives.

    Same reduction algebra as bignum.BarrettCtx (HAC Alg. 14.42) - the mu
    and m products ride constant Toeplitz matmuls, carries use the
    lookahead path, and the two trailing conditional subtractions use the
    radix-complement trick. All per-modulus constants are passed to the
    module-level kernels as OPERANDS so compiled executables are shared
    across moduli of a width (critical on a 1-core host: one compile per
    shape, hit by every party/key/process via the persistent cache).

    The modulus need NOT occupy the top limb (profiles are block-padded);
    the Barrett shift windows derive from the modulus' true occupancy.
    """

    def __init__(self, modulus: int, n_limbs: Optional[int] = None):
        self.modulus = modulus
        mb = modulus.bit_length()
        occ = -(-mb // LIMB_BITS)  # limbs the modulus actually occupies
        self.prof = (
            bn.LimbProfile(bits=LIMB_BITS, n_limbs=n_limbs)
            if n_limbs
            else profile(mb)
        )
        n = self.prof.n_limbs
        assert occ <= n
        self.occ = occ
        # Barrett: mu = floor(R^(2*occ) / m); q1 = x >> (occ-1) limbs;
        # q3 = (q1*mu) >> (occ+1) limbs; r = x - q3*m over occ+1 limbs.
        self.mu = (1 << (2 * occ * LIMB_BITS)) // modulus
        self._T_mu = _const_matrices(self.mu, 2 * n - (occ - 1))
        self._T_m = _const_matrices(modulus, 2 * n)
        comp = (1 << ((occ + 1) * LIMB_BITS)) - modulus
        self._comp = jnp.asarray(
            bn.to_limbs(comp, self.prof, n_limbs=occ + 2), jnp.int32
        )
        self._m1 = jnp.asarray(
            bn.to_limbs(modulus, self.prof, occ + 1), jnp.int32
        )
        self.m_limbs = bn.to_limbs(modulus, self.prof)
        self._fb_tables: Dict = {}
        # named operands (comb tables, constant Toeplitz matrices, digit
        # arrays of constant exponents): what a jitted round program may
        # ask this context for by NAME, because inside a trace the context
        # is rebuilt from its arrays alone (see _tree_flatten) and holds
        # no python integer of the key
        self._named: Dict[str, jnp.ndarray] = {}

    # -- pytree: a context is an ARGUMENT of the jitted round programs ------

    def _tree_flatten(self):
        """Arrays are the children; only widths and names are static, so
        one compiled program serves every modulus of a width and no key
        material lands in a compiled executable or the compile cache."""
        names = tuple(sorted(self._named))
        children = (self._T_mu, self._T_m, self._comp, self._m1,
                    tuple(self._named[k] for k in names))
        return children, (self.occ, self.prof.n_limbs, names)

    @classmethod
    def _tree_unflatten(cls, aux, children):
        self = object.__new__(cls)
        self.occ, n, names = aux
        self.prof = bn.LimbProfile(bits=LIMB_BITS, n_limbs=n)
        self.modulus = None  # not known inside a trace, by design
        self._T_mu, self._T_m, self._comp, self._m1, named = children
        self._named = dict(zip(names, named))
        self._fb_tables = None
        return self

    def name_comb(self, name: str, base: int, n_bits: int) -> None:
        """Keep the comb table of ``base`` for exponents of up to
        ``n_bits`` under ``name`` (rebuilt only to grow)."""
        nw = -(-n_bits // COMB_W)
        have = self._named.get(name)
        if have is None or have.shape[0] < nw:
            self._named[name] = self._comb_table(base, nw)

    def name_const(self, name: str, value: int) -> None:
        if name not in self._named:
            self._named[name] = self._const_T(value)

    def name_exponent(self, name: str, exponent: int) -> None:
        if name not in self._named:
            self._named[name] = _exp_digits(exponent)

    # -- audit --------------------------------------------------------------

    def _audit(self, op: str, mulmods: float) -> None:
        """Record mulmod-equivalent dispatch counts into the module-level
        AUDIT dict (None = disabled, zero overhead). Key: (op, modulus
        bits). Used by .scratch/audit_counts.py to budget where the
        per-signature mulmods go without needing the chip."""
        if AUDIT is not None:
            k = (op, self.occ * LIMB_BITS)
            AUDIT[k] = AUDIT.get(k, 0.0) + mulmods

    # -- helpers ------------------------------------------------------------

    def const(self, value: int, batch_shape=()) -> jnp.ndarray:
        v = jnp.asarray(bn.to_limbs(value % self.modulus, self.prof))
        return jnp.broadcast_to(v, tuple(batch_shape) + (self.prof.n_limbs,))

    def one_like(self, x: jnp.ndarray) -> jnp.ndarray:
        return _one_like(x, self.prof.n_limbs)

    # -- core ---------------------------------------------------------------

    def reduce(self, x: jnp.ndarray) -> jnp.ndarray:
        self._audit("reduce", 0.5)
        return _k_reduce(
            x, self._T_mu, self._T_m, self._comp, self.occ, self.prof.n_limbs
        )

    def mulmod(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        self._audit("mulmod", 1)
        return _k_mulmod(
            a, b, self._T_mu, self._T_m, self._comp, self.occ,
            self.prof.n_limbs,
        )

    def sqrmod(self, a: jnp.ndarray) -> jnp.ndarray:
        return self.mulmod(a, a)

    def _const_T(self, value: int) -> jnp.ndarray:
        key = ("constT", value % self.modulus)
        T = self._fb_tables.get(key)
        if T is None:
            # pad the constant to occ limbs so every constant of this
            # modulus shares one kernel shape
            T = _const_matrices(
                value % self.modulus, self.prof.n_limbs, min_limbs=self.occ
            )
            self._fb_tables[key] = T
            size = sum(int(t.nbytes) for t in jax.tree.leaves(T))  # mpcflow: declassified — a table's size in bytes is its shape, not its values
            bits = self.modulus.bit_length()  # mpcflow: declassified — a modulus' width is public
            _track_fb_table(size, "constT", bits)
        return T

    def mulmod_const(self, a: jnp.ndarray, value: int) -> jnp.ndarray:
        """a times a python-int constant (cached width-padded Toeplitz)."""
        return self._mulmod_T(a, self._const_T(value))

    def mulmod_named(self, a: jnp.ndarray, name: str) -> jnp.ndarray:
        """a times the constant kept under ``name`` (:meth:`name_const`)."""
        return self._mulmod_T(a, self._named[name])

    def _mulmod_T(self, a: jnp.ndarray, T: jnp.ndarray) -> jnp.ndarray:
        self._audit("mulmod_const", 0.5)
        return _k_mulmod_const(
            a, T, self._T_mu, self._T_m, self._comp, self.occ,
            self.prof.n_limbs,
        )

    def addmod(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        return _k_addmod(a, b, self._comp, self.occ, self.prof.n_limbs)

    def submod(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        return _k_submod(
            a, b, self._m1, self._comp, self.occ, self.prof.n_limbs
        )

    def negmod(self, a: jnp.ndarray) -> jnp.ndarray:
        return self.submod(jnp.zeros_like(a), a)

    # -- exponentiation -----------------------------------------------------

    def powmod_const_exp(self, x: jnp.ndarray, exponent: int) -> jnp.ndarray:
        """x^e mod m for a batch-shared python-int exponent (digit array is
        a runtime operand: one compile per digit count, any value)."""
        if exponent == 0:
            return self.one_like(x)
        return self._powmod_digits(x, _exp_digits(exponent))

    def powmod_named_exp(self, x: jnp.ndarray, name: str) -> jnp.ndarray:
        """x^e mod m for the exponent kept under ``name``
        (:meth:`name_exponent`)."""
        return self._powmod_digits(x, self._named[name])

    def _powmod_digits(self, x: jnp.ndarray, digits) -> jnp.ndarray:
        nw = digits.shape[0]
        self._audit(f"powmod_const_exp/e{4 * nw}", 5 * nw + 14)
        return _k_powmod_digits(
            x, digits, self._T_mu, self._T_m, self._comp, self.occ,
            self.prof.n_limbs,
        )

    def powmod(self, x: jnp.ndarray, ebits: jnp.ndarray) -> jnp.ndarray:
        """x^e with per-element exponent bits (LSB-first), 4-bit windows."""
        self._audit(
            f"powmod/e{ebits.shape[-1]}",
            5 * (-(-ebits.shape[-1] // 4)) + 14,
        )
        return _k_powmod(
            x, ebits, self._T_mu, self._T_m, self._comp, self.occ,
            self.prof.n_limbs,
        )

    def powmod_fixed_base(self, base: int, ebits: jnp.ndarray) -> jnp.ndarray:
        """base^e mod m, python-int base, per-element exponent bits.
        Comb tables base^(2^(w·i) · d), built once on the device
        (:meth:`_comb_table`): ONE mulmod per w-bit window, no squarings
        (the ring-Pedersen commitment workhorse). Window width COMB_W
        (default 8): halving the mulmod count vs w=4 at the price of
        2^w-row tables — ~100 MB per (base, 2048-bit modulus) for a
        2400-bit exponent in the int32 limb layout (300 windows x 256
        rows x 320 limbs x 4 B), device-resident once per process; budget
        ~200 MB per counterparty NTilde (h1+h2) when sizing HBM."""
        nw = -(-ebits.shape[-1] // COMB_W)
        return self._powmod_comb(self._comb_table(base, nw), ebits)

    def powmod_named_base(self, name: str, ebits: jnp.ndarray) -> jnp.ndarray:
        """base^e mod m for the comb kept under ``name``
        (:meth:`name_comb`): a comb's first windows are the comb of a
        shorter exponent, a static slice of the operand."""
        nw = -(-ebits.shape[-1] // COMB_W)
        return self._powmod_comb(self._named[name][:nw], ebits)

    def _powmod_comb(self, tbl: jnp.ndarray, ebits: jnp.ndarray):
        self._audit(f"powmod_fixed_base/e{ebits.shape[-1]}", tbl.shape[0])
        return _k_powmod_fb(
            tbl, ebits, self._T_mu, self._T_m, self._comp, self.occ,
            self.prof.n_limbs,
        )

    def _comb_table(self, base: int, nw: int) -> jnp.ndarray:
        """tbl[i, w] = base^(w·2^(COMB_W·i)) mod m, (nw, 2^COMB_W, n_limbs)
        canonical limbs, made on the device: the host computes the nw
        window bases b_i = b_(i-1)^(2^COMB_W) (short and sequential), the
        rows of every window are :func:`_k_comb_rows`' (the table never
        exists on the host and is never transferred)."""
        key = (base % self.modulus, nw, COMB_W)
        tbl = self._fb_tables.get(key)
        if tbl is None:
            m, n = self.modulus, self.prof.n_limbs
            b_i, bases = base % m, []
            for _ in range(nw):
                bases.append(b_i)
                b_i = pow(b_i, 1 << COMB_W, m)
            lanes = next((k for k in _COMB_LANES if nw <= k), _COMB_LANES[-1])
            limbs = np.zeros((-(-nw // lanes) * lanes, n), np.int32)
            limbs[:nw] = ints_to_limbs(bases, self.prof)
            chunks = tuple(
                _k_comb_rows(
                    jnp.asarray(limbs[at:at + lanes]), self._T_mu, self._T_m,
                    self._comp, self.occ, n,
                )
                for at in range(0, len(limbs), lanes)
            )
            tbl = chunks[0] if nw == lanes else _k_comb_take(chunks, nw)
            self._fb_tables[key] = tbl
            size = int(tbl.nbytes)  # mpcflow: host-ok — a size from the shape, no transfer
            bits = self.modulus.bit_length()  # mpcflow: declassified — a modulus' width is public
            _track_fb_table(size, "comb", bits)
        return tbl

    # -- several exponentiations as ONE ladder ------------------------------
    #
    # A ladder's step costs nearly the same for 32 lanes as for 256 (its
    # launch, the constants' way into fast memory and the MXU's weight
    # loads do not grow with the lanes), and a program runs its ladders
    # one after another. So independent exponentiations in one modulus
    # are stacked on the lane axis, their exponents zero-extended at the
    # top to the widest (a leading zero window multiplies by one), and
    # run as one ladder: the steps of the longest, not the sum of all.

    @staticmethod
    def _stack_bits(ebits_list):
        width = max(e.shape[-1] for e in ebits_list)
        return jnp.concatenate([
            jnp.pad(e, ((0, 0), (0, width - e.shape[-1])))
            for e in ebits_list
        ], axis=0)

    @staticmethod
    def _unstack(x: jnp.ndarray, sizes) -> list:
        out, at = [], 0
        for k in sizes:
            out.append(x[at:at + k])
            at += k
        return out

    def powmod_many(self, pairs) -> list:
        """[(x, ebits), ...] → [x^e, ...]: one ladder over all lanes."""
        xs = jnp.concatenate([x for x, _ in pairs], axis=0)
        out = self.powmod(xs, self._stack_bits([e for _, e in pairs]))
        return self._unstack(out, [x.shape[0] for x, _ in pairs])

    def powmod_named_base_many(self, name: str, ebits_list) -> list:
        """[ebits, ...] → [base^e, ...]: one comb pass over all lanes."""
        out = self.powmod_named_base(name, self._stack_bits(ebits_list))
        return self._unstack(out, [e.shape[0] for e in ebits_list])

    def mulmod_many(self, pairs) -> list:
        """[(a, b), ...] → [a·b, ...]: one product over all lanes."""
        out = self.mulmod(
            jnp.concatenate([a for a, _ in pairs], axis=0),
            jnp.concatenate([b for _, b in pairs], axis=0),
        )
        return self._unstack(out, [a.shape[0] for a, _ in pairs])

    def invmod_prime(self, x: jnp.ndarray) -> jnp.ndarray:
        return self.powmod_const_exp(x, self.modulus - 2)

    # -- batch product reduction (for randomized batch verification) --------

    def prod_over_batch(self, x: jnp.ndarray, axis: int = 0) -> jnp.ndarray:
        """Product of x_b mod m along ``axis`` by log-depth pairwise folds."""
        x = jnp.moveaxis(x, axis, 0)
        # (no _audit here: the fold's mulmod calls audit themselves)
        while x.shape[0] > 1:
            k = x.shape[0]
            if k % 2:
                x = jnp.concatenate([x, self.one_like(x[0])[None]], axis=0)
                k += 1
            x = self.mulmod(x[: k // 2], x[k // 2:])
        return x[0]


jax.tree_util.register_pytree_node(
    MXUBarrett, MXUBarrett._tree_flatten, MXUBarrett._tree_unflatten
)
