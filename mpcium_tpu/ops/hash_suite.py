"""Device hash suite: one Merkle–Damgård engine, four hot-path kernels.

``ops/sha256.py`` proved that FIPS 180-4 compression lowers well to
vmapped uint32 lanes (32-bit message schedule + 64 rounds under
``lax.scan``). This module generalizes that proof into the shared
engine behind every hashing hot path the budget tracks (ROADMAP item 2;
HOST_TRANSFER_BUDGET.json):

* **SHA-256** — the existing kernel, factored here; ``ops.sha256``
  delegates so its public API is unchanged.
* **SHA-512** — 64-bit lanes as ``(hi, lo)`` uint32 limb pairs with
  explicit carry, because JAX defaults to 32-bit ints and the TPU has
  no native 64-bit integer path; 80 rounds, 128-byte blocks. The
  RFC 8032 challenge of ``engine/eddsa_batch.py`` (``challenge_device``)
  runs through ``sha512_masked``: rows of DIFFERENT lengths in one
  program, the lengths data and the width a rung of ``SHA512_RUNGS``, so
  raw messages (a Solana transaction message is 150–1,167 bytes) never
  compile by length and never go back to the host. ``sha512_fixed`` (one
  static length) stays for the per-session path of
  ``protocol/eddsa/signing.py`` and as a reference.
* **PRG expansion** (``prg_expand_device``) — the IKNP seed→keystream
  expansion ``sha256(prefix ‖ seed ‖ le16(j) ‖ le32(blk))``,
  byte-identical to ``native.prg_expand`` / ``mta_ot._prg``, batched
  over (seed, block) on device.
* **Packed bit-transpose** (``ot_transpose_device``) — the (κ, M/8) ↔
  (M, κ/8) little-bitorder transpose that cost a ~130 MB strided host
  copy per extension leg in the numpy fallback.
* **Pad hash** (``pad_hash_core``) — the per-OT correlation hash
  ``H(prefix ‖ row ‖ le32(index))`` of ``mta_ot._derive_pads_multi``.

Everything here is a pure trace function plus a thin jitted wrapper, so
``mta_ot``'s device extension path can fuse PRG + transpose + pads +
masking into ONE dispatch per chunk. Domain prefixes are TRACED uint8
arrays, never static arguments: the OT tags embed a per-invocation
counter, and a static prefix would recompile every extension (the
executable is shape-keyed only — one compile per (prefix length,
batch shape) bucket).

Transcript discipline: these kernels change WHERE bytes are computed,
never the bytes. tests/test_hash_suite.py pins them against
hashlib/native/NumPy on FIPS vectors and ragged shapes, and
tests/test_mta_ot_pipeline.py + test_mta_ot_device.py prove the OT
transcripts bit-identical to the host path (OT_WIRE_VERSION stays 2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# ---------------------------------------------------------------------------
# constants (FIPS 180-4): derived from prime roots with integer
# arithmetic — no float precision, no 80-entry transcription risk
# ---------------------------------------------------------------------------


def _primes(n: int):
    out, c = [], 2
    while len(out) < n:
        if all(c % p for p in out):
            out.append(c)
        c += 1
    return out


def _icbrt(n: int) -> int:
    x = 1 << -(-n.bit_length() // 3)
    while True:
        y = (2 * x + n // (x * x)) // 3
        if y >= x:
            return x
        x = y


def _isqrt(n: int) -> int:
    import math

    return math.isqrt(n)


_P80 = _primes(80)

# SHA-256: frac(cbrt(p)) · 2^32 and frac(sqrt(p)) · 2^32
_K256 = np.array(
    [_icbrt(p << 96) & 0xFFFFFFFF for p in _P80[:64]], dtype=np.uint32
)
_H256 = np.array(
    [_isqrt(p << 64) & 0xFFFFFFFF for p in _P80[:8]], dtype=np.uint32
)

# SHA-512: frac(cbrt(p)) · 2^64 and frac(sqrt(p)) · 2^64, as (hi, lo)
# uint32 pairs (JAX default dtypes are 32-bit; TPUs have no int64 lanes)
_K512_INT = [_icbrt(p << 192) & 0xFFFFFFFFFFFFFFFF for p in _P80]
_H512_INT = [_isqrt(p << 128) & 0xFFFFFFFFFFFFFFFF for p in _P80[:8]]
_K512_HI = np.array([k >> 32 for k in _K512_INT], dtype=np.uint32)
_K512_LO = np.array([k & 0xFFFFFFFF for k in _K512_INT], dtype=np.uint32)
_H512_HI = np.array([h >> 32 for h in _H512_INT], dtype=np.uint32)
_H512_LO = np.array([h & 0xFFFFFFFF for h in _H512_INT], dtype=np.uint32)

assert _K256[0] == 0x428A2F98 and _H256[0] == 0x6A09E667
assert _K512_INT[0] == 0x428A2F98D728AE22
assert _H512_INT[0] == 0x6A09E667F3BCC908


# ---------------------------------------------------------------------------
# SHA-256 core (factored from ops/sha256.py)
# ---------------------------------------------------------------------------


def _rotr32(x: jnp.ndarray, n: int) -> jnp.ndarray:
    return (x >> n) | (x << (32 - n))


def sha256_compress(state: jnp.ndarray, block: jnp.ndarray) -> jnp.ndarray:
    """state (..., 8) uint32, block (..., 16) uint32 → new state."""

    def sched(carry_w, _):
        w = carry_w  # (..., 16) rolling window
        s0 = _rotr32(w[..., 1], 7) ^ _rotr32(w[..., 1], 18) ^ (w[..., 1] >> 3)
        s1 = (
            _rotr32(w[..., 14], 17)
            ^ _rotr32(w[..., 14], 19)
            ^ (w[..., 14] >> 10)
        )
        nxt = w[..., 0] + s0 + w[..., 9] + s1
        return jnp.concatenate([w[..., 1:], nxt[..., None]], axis=-1), w[..., 0]

    # words 0..63: first 16 from the block, rest from the rolling schedule
    _, w_all = lax.scan(sched, block, None, length=64)
    # w_all: (64, ...) — word t of the schedule

    def round_step(st, wk):
        w_t, k_t = wk
        a, b, c, d, e, f, g, h = [st[..., i] for i in range(8)]
        S1 = _rotr32(e, 6) ^ _rotr32(e, 11) ^ _rotr32(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + S1 + ch + k_t + w_t
        S0 = _rotr32(a, 2) ^ _rotr32(a, 13) ^ _rotr32(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = S0 + maj
        return jnp.stack(
            [t1 + t2, a, b, c, d + t1, e, f, g], axis=-1
        ), None

    out, _ = lax.scan(round_step, state, (w_all, jnp.asarray(_K256)))
    return state + out


def bytes_to_words32(b: jnp.ndarray) -> jnp.ndarray:
    """(..., 4k) uint8 big-endian → (..., k) uint32."""
    k = b.shape[-1] // 4
    w = b.reshape(b.shape[:-1] + (k, 4)).astype(jnp.uint32)
    return (w[..., 0] << 24) | (w[..., 1] << 16) | (w[..., 2] << 8) | w[..., 3]


def words32_to_bytes(w: jnp.ndarray) -> jnp.ndarray:
    out = jnp.stack(
        [(w >> 24) & 0xFF, (w >> 16) & 0xFF, (w >> 8) & 0xFF, w & 0xFF],
        axis=-1,
    ).astype(jnp.uint8)
    return out.reshape(w.shape[:-1] + (w.shape[-1] * 4,))


def _md_pad(data: jnp.ndarray, msg_len: int, block: int, len_bytes: int):
    """Merkle–Damgård strengthening: 0x80, zeros, big-endian bit length
    in the trailing ``len_bytes`` — shared by both widths."""
    pad_total = (-(msg_len + 1 + len_bytes)) % block + 1 + len_bytes
    batch = data.shape[:-1]
    pad = jnp.zeros(batch + (pad_total,), jnp.uint8)
    pad = pad.at[..., 0].set(0x80)
    bitlen = msg_len * 8
    lenb = jnp.asarray(
        [(bitlen >> (8 * i)) & 0xFF for i in range(7, -1, -1)], jnp.uint8
    )
    pad = pad.at[..., -8:].set(jnp.broadcast_to(lenb, batch + (8,)))
    return jnp.concatenate([data, pad], axis=-1)


def sha256_core(data: jnp.ndarray, msg_len: int) -> jnp.ndarray:
    """Pure trace function: (..., msg_len) uint8 → (..., 32) digests.
    Callers embedding this in a larger jitted kernel use it directly;
    standalone callers go through :func:`sha256`."""
    full = _md_pad(data, msg_len, 64, 8)
    words = bytes_to_words32(full)  # (..., 16·n_blocks)
    n_blocks = words.shape[-1] // 16
    state = jnp.broadcast_to(jnp.asarray(_H256), data.shape[:-1] + (8,))
    # the blocks through ONE compiled compression (a GG18 challenge
    # hashes ~40 blocks a row: unrolled, that is 40 copies in a program)
    blocks = jnp.moveaxis(
        words.reshape(words.shape[:-1] + (n_blocks, 16)), -2, 0
    )
    state, _ = lax.scan(
        lambda st, blk: (sha256_compress(st, blk), None), state, blocks
    )
    return words32_to_bytes(state)


@functools.partial(jax.jit, static_argnames=("msg_len",))
def sha256_fixed(data: jnp.ndarray, msg_len: int) -> jnp.ndarray:
    """data (..., msg_len) uint8 → (..., 32) uint8 digests."""
    return sha256_core(data, msg_len)


def sha256(data: jnp.ndarray) -> jnp.ndarray:
    """Batched SHA-256 over the last axis: (..., L) uint8 → (..., 32)."""
    return sha256_fixed(data, data.shape[-1])


# ---------------------------------------------------------------------------
# SHA-512 core: 64-bit words as (hi, lo) uint32 pairs
# ---------------------------------------------------------------------------
#
# Every 64-bit quantity is a pair of same-shaped uint32 arrays. Addition
# carries explicitly (uint32 wraps, carry = lo_sum < lo_a); rotates and
# shifts branch STATICALLY on the amount, so each lowers to two shifts
# and an or — no 64-bit emulation library, just the five ops SHA-512
# needs.


def _add64(ah, al, bh, bl):
    lo = al + bl
    carry = (lo < al).astype(jnp.uint32)
    return ah + bh + carry, lo


def _rotr64(h, l, n: int):  # noqa: E741 — l is the low word
    if n == 0:
        return h, l
    if n == 32:
        return l, h
    if n > 32:
        return _rotr64(l, h, n - 32)
    return (
        (h >> n) | (l << (32 - n)),
        (l >> n) | (h << (32 - n)),
    )


def _shr64(h, l, n: int):  # noqa: E741
    if n == 0:
        return h, l
    if n >= 32:
        return jnp.zeros_like(h), h >> (n - 32) if n > 32 else h
    return h >> n, (l >> n) | (h << (32 - n))


def _xor3(a, b, c):
    return (a[0] ^ b[0] ^ c[0], a[1] ^ b[1] ^ c[1])


def sha512_compress(state_h, state_l, block_h, block_l):
    """state (..., 8)×2 uint32, block (..., 16)×2 uint32 → new state."""

    def sched(carry, _):
        wh, wl = carry  # (..., 16) rolling windows
        s0 = _xor3(
            _rotr64(wh[..., 1], wl[..., 1], 1),
            _rotr64(wh[..., 1], wl[..., 1], 8),
            _shr64(wh[..., 1], wl[..., 1], 7),
        )
        s1 = _xor3(
            _rotr64(wh[..., 14], wl[..., 14], 19),
            _rotr64(wh[..., 14], wl[..., 14], 61),
            _shr64(wh[..., 14], wl[..., 14], 6),
        )
        nh, nl = _add64(
            *_add64(*_add64(wh[..., 0], wl[..., 0], *s0),
                    wh[..., 9], wl[..., 9]),
            *s1,
        )
        return (
            jnp.concatenate([wh[..., 1:], nh[..., None]], axis=-1),
            jnp.concatenate([wl[..., 1:], nl[..., None]], axis=-1),
        ), (wh[..., 0], wl[..., 0])

    _, (w_all_h, w_all_l) = lax.scan(
        sched, (block_h, block_l), None, length=80
    )

    def round_step(st, wk):
        sh, sl = st
        w_h, w_l, k_h, k_l = wk
        ah, bh, ch_, dh, eh, fh, gh, hh = [sh[..., i] for i in range(8)]
        al, bl, cl, dl, el, fl, gl, hl = [sl[..., i] for i in range(8)]
        S1 = _xor3(
            _rotr64(eh, el, 14), _rotr64(eh, el, 18), _rotr64(eh, el, 41)
        )
        chh = (eh & fh) ^ (~eh & gh)
        chl = (el & fl) ^ (~el & gl)
        t1 = _add64(
            *_add64(*_add64(*_add64(hh, hl, *S1), chh, chl), k_h, k_l),
            w_h, w_l,
        )
        S0 = _xor3(
            _rotr64(ah, al, 28), _rotr64(ah, al, 34), _rotr64(ah, al, 39)
        )
        majh = (ah & bh) ^ (ah & ch_) ^ (bh & ch_)
        majl = (al & bl) ^ (al & cl) ^ (bl & cl)
        t2 = _add64(*S0, majh, majl)
        nah, nal = _add64(*t1, *t2)
        neh, nel = _add64(dh, dl, *t1)
        return (
            jnp.stack([nah, ah, bh, ch_, neh, eh, fh, gh], axis=-1),
            jnp.stack([nal, al, bl, cl, nel, el, fl, gl], axis=-1),
        ), None

    (out_h, out_l), _ = lax.scan(
        round_step,
        (state_h, state_l),
        (w_all_h, w_all_l, jnp.asarray(_K512_HI), jnp.asarray(_K512_LO)),
    )
    return _add64(state_h, state_l, out_h, out_l)


def sha512_core(data: jnp.ndarray, msg_len: int) -> jnp.ndarray:
    """Pure trace function: (..., msg_len) uint8 → (..., 64) digests.
    128-byte blocks; the 16-byte length field's high quadword is zero
    (messages here are far below 2^64 bits)."""
    full = _md_pad(data, msg_len, 128, 16)
    words = bytes_to_words32(full)  # (..., 32·n_blocks) — BE uint32 halves
    n_blocks = words.shape[-1] // 32
    batch = data.shape[:-1]
    sh = jnp.broadcast_to(jnp.asarray(_H512_HI), batch + (8,))
    sl = jnp.broadcast_to(jnp.asarray(_H512_LO), batch + (8,))
    for i in range(n_blocks):
        blk = words[..., 32 * i : 32 * (i + 1)]
        sh, sl = sha512_compress(sh, sl, blk[..., 0::2], blk[..., 1::2])
    # interleave (hi, lo) back into 16 BE uint32 words → 64 bytes
    out = jnp.stack([sh, sl], axis=-1).reshape(batch + (16,))
    return words32_to_bytes(out)


@functools.partial(jax.jit, static_argnames=("msg_len",))
def sha512_fixed(data: jnp.ndarray, msg_len: int) -> jnp.ndarray:
    return sha512_core(data, msg_len)


def sha512(data: jnp.ndarray) -> jnp.ndarray:
    """Batched SHA-512 over the last axis: (..., L) uint8 → (..., 64)."""
    return sha512_fixed(data, data.shape[-1])


# -- rows of different lengths in one program --------------------------------
#
# ``sha512_fixed`` compiles once per message length. A batch of RFC 8032
# challenges over raw messages (a Solana transaction message is 150 to
# 1,167 bytes, and the lengths differ inside one batch) needs the length
# to be DATA: the rows come zero-filled to a common width, the padding is
# placed per lane, every block of the width is compressed, and a lane
# keeps the state it had after its own last block. The width comes from a
# short ladder of block counts, so a process compiles one program per
# (batch, rung) whatever lengths arrive.

SHA512_RUNGS = (1, 2, 4, 8, 16)  # blocks of 128 bytes
_SHA512_TAIL = 17  # the 0x80 byte and the 16-byte bit length


def sha512_rung_cap(longest: int):
    """The row width (bytes) of the smallest rung that holds a row of
    ``longest`` bytes with its padding: ``blocks * 128 - 17``. ``None``
    past the top rung (2,031 bytes)."""
    for blocks in SHA512_RUNGS:
        if longest + _SHA512_TAIL <= blocks * 128:
            return blocks * 128 - _SHA512_TAIL
    return None


def sha512_masked_blocks(cap: int) -> int:
    """128-byte blocks :func:`sha512_masked` compresses for every lane of
    rows ``cap`` bytes wide."""
    return -(-(cap + _SHA512_TAIL) // 128)


def sha512_masked_core(rows: jnp.ndarray, lens: jnp.ndarray) -> jnp.ndarray:
    """Pure trace function: ``rows`` (..., cap) uint8, ``lens`` (...,)
    int32 with ``0 <= lens <= cap`` → (..., 64) digests of each lane's
    first ``lens`` bytes. Bytes past a lane's length are ignored."""
    batch, cap = rows.shape[:-1], rows.shape[-1]
    n_blocks = sha512_masked_blocks(cap)
    width = n_blocks * 128
    lens = lens.astype(jnp.int32)
    pos = jnp.arange(width, dtype=jnp.int32)
    full = jnp.concatenate(
        [rows, jnp.zeros(batch + (width - cap,), jnp.uint8)], axis=-1
    )
    at = lens[..., None]
    full = jnp.where(
        pos < at, full, jnp.where(pos == at, jnp.uint8(0x80), jnp.uint8(0))
    )
    words = bytes_to_words32(full)  # (..., 32·n_blocks) BE uint32 halves
    # the lane's last block is the one its bit length ends: 64-bit length
    # in the last two words (the field's high quadword stays zero)
    last = (lens + (_SHA512_TAIL - 1)) // 128
    widx = jnp.arange(n_blocks * 32, dtype=jnp.int32)
    ulen = lens.astype(jnp.uint32)[..., None]
    end = (last * 32 + 31)[..., None]
    words = words | jnp.where(widx == end, ulen << 3, jnp.uint32(0))
    words = words | jnp.where(widx == end - 1, ulen >> 29, jnp.uint32(0))
    blocks = jnp.moveaxis(
        words.reshape(batch + (n_blocks, 32)), -2, 0
    )  # (n_blocks, ..., 32)
    sh = jnp.broadcast_to(jnp.asarray(_H512_HI), batch + (8,))
    sl = jnp.broadcast_to(jnp.asarray(_H512_LO), batch + (8,))

    def step(st, xs):
        i, blk = xs
        nh, nl = sha512_compress(*st, blk[..., 0::2], blk[..., 1::2])
        live = (i <= last)[..., None]
        return (jnp.where(live, nh, st[0]), jnp.where(live, nl, st[1])), None

    # the blocks through ONE compiled compression, as sha256_core's
    (sh, sl), _ = lax.scan(
        step, (sh, sl), (jnp.arange(n_blocks, dtype=jnp.int32), blocks)
    )
    out = jnp.stack([sh, sl], axis=-1).reshape(batch + (16,))
    return words32_to_bytes(out)


@jax.jit
def sha512_masked(rows: jnp.ndarray, lens: jnp.ndarray) -> jnp.ndarray:
    """Batched SHA-512 over rows of different lengths: ``rows`` (..., cap)
    uint8 zero-filled past each lane's length, ``lens`` (...,) int32 →
    (..., 64) uint8. One compile per (batch shape, ``cap``); callers take
    ``cap`` from :func:`sha512_rung_cap`, so lengths never compile."""
    return sha512_masked_core(rows, lens)


def sha512_bytes(data: bytes) -> bytes:
    """Single-message device SHA-512 → 64 digest bytes. The per-session
    protocol path (protocol/eddsa/signing.py) can route its RFC 8032
    challenge through the batched kernel with this; the batch engines
    use :func:`sha512` directly and never leave the device."""
    arr = jnp.asarray(np.frombuffer(data, np.uint8))
    return bytes(np.asarray(sha512(arr)))  # mpcflow: host-ok — single-digest egress for the host protocol caller


# ---------------------------------------------------------------------------
# OT hot-path kernels (PRG expansion, packed transpose, pad hash)
# ---------------------------------------------------------------------------


def le16_bytes(x: jnp.ndarray) -> jnp.ndarray:
    """uint32 (...,) → (..., 2) little-endian uint8."""
    return jnp.stack([x & 0xFF, (x >> 8) & 0xFF], axis=-1).astype(jnp.uint8)


def le32_bytes(x: jnp.ndarray) -> jnp.ndarray:
    """uint32 (...,) → (..., 4) little-endian uint8."""
    return jnp.stack(
        [(x >> (8 * i)) & 0xFF for i in range(4)], axis=-1
    ).astype(jnp.uint8)


def prg_expand_core(
    seeds: jnp.ndarray, prefix: jnp.ndarray, nblk: int, blk_off
) -> jnp.ndarray:
    """Trace function: (n, 32) uint8 seeds → (n, nblk·32) keystream,
    block (j, b) = sha256(prefix ‖ seed_j ‖ le16(j) ‖ le32(blk_off+b)) —
    the exact message layout of ``native.prg_expand`` / ``mta_ot._prg``.
    ``prefix`` is a traced (P,) uint8 array (OT tags embed a counter);
    ``blk_off`` is a traced scalar (chunked callers slide it)."""
    n = seeds.shape[0]
    P = prefix.shape[0]
    j_le = le16_bytes(jnp.arange(n, dtype=jnp.uint32))  # (n, 2)
    blk = jnp.asarray(blk_off, jnp.uint32) + jnp.arange(nblk, dtype=jnp.uint32)
    blk_le = le32_bytes(blk)  # (nblk, 4)
    msg = jnp.concatenate(
        [
            jnp.broadcast_to(prefix, (n, nblk, P)),
            jnp.broadcast_to(seeds[:, None, :], (n, nblk, 32)),
            jnp.broadcast_to(j_le[:, None, :], (n, nblk, 2)),
            jnp.broadcast_to(blk_le[None, :, :], (n, nblk, 4)),
        ],
        axis=-1,
    )
    return sha256_core(msg, P + 38).reshape(n, nblk * 32)


@functools.partial(jax.jit, static_argnames=("nblk",))
def _prg_expand_jit(seeds, prefix, blk_off, nblk):
    return prg_expand_core(seeds, prefix, nblk, blk_off)


def prg_expand_device(
    prefix: bytes, seeds, nblk: int, blk_off: int = 0
) -> jnp.ndarray:
    """Standalone entry matching ``native.prg_expand``'s signature:
    (n_seeds, 32) uint8 → (n_seeds, nblk·32) device keystream."""
    pre = jnp.asarray(np.frombuffer(prefix, np.uint8))
    return _prg_expand_jit(
        jnp.asarray(seeds), pre, jnp.uint32(blk_off), nblk
    )


def pack_bits_core(bits: jnp.ndarray) -> jnp.ndarray:
    """(..., 8k) 0/1 → (..., k) packed little-bitorder uint8 (device
    twin of np.packbits(..., bitorder="little"))."""
    k = bits.shape[-1] // 8
    w = jnp.left_shift(
        jnp.uint32(1), jnp.arange(8, dtype=jnp.uint32)
    )
    grouped = bits.reshape(bits.shape[:-1] + (k, 8)).astype(jnp.uint32)
    return (grouped * w).sum(axis=-1).astype(jnp.uint8)


def unpack_bits_core(packed: jnp.ndarray) -> jnp.ndarray:
    """(..., k) uint8 → (..., 8k) 0/1 uint8, little bitorder."""
    bits = (
        packed[..., None] >> jnp.arange(8, dtype=jnp.uint8)[None, :]
    ) & 1
    return bits.reshape(packed.shape[:-1] + (packed.shape[-1] * 8,))


def ot_transpose_core(packed: jnp.ndarray) -> jnp.ndarray:
    """Trace function: (R, C) packed little-bitorder bytes → the packed
    transpose (C·8, R/8) — unpack, transpose, repack, all fused by XLA
    (no ~130 MB strided host copy; R must be a multiple of 8)."""
    R, C = packed.shape
    bits = unpack_bits_core(packed)  # (R, 8C)
    return pack_bits_core(bits.T)  # (8C, R) → (8C, R/8)


ot_transpose_device = jax.jit(ot_transpose_core)


def pad_hash_core(
    prefix: jnp.ndarray, rows: jnp.ndarray, idx_le: jnp.ndarray
) -> jnp.ndarray:
    """Trace function: per-OT correlation pads
    H(prefix ‖ row_j ‖ le32(index_j)) → (M, 32); the device twin of
    ``mta_ot._derive_pads_multi``'s per-prefix hash."""
    M = rows.shape[0]
    msg = jnp.concatenate(
        [jnp.broadcast_to(prefix, (M, prefix.shape[0])), rows, idx_le],
        axis=-1,
    )
    return sha256_core(msg, msg.shape[-1])


@jax.jit
def pad_hash_device(prefix, rows, m_off):
    idx = le32_bytes(
        jnp.asarray(m_off, jnp.uint32)
        + jnp.arange(rows.shape[0], dtype=jnp.uint32)
    )
    return pad_hash_core(prefix, rows, idx)
