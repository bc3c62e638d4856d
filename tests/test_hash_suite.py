"""Device hash suite (ISSUE 11): every kernel in ops.hash_suite must be
byte-identical to its host oracle — hashlib for the FIPS 180-4 digests,
numpy packbits for the packed transpose, and mta_ot's host PRG / pad
derivation for the OT kernels. These are the proofs that let the device
OT path and the eddsa device hashes ship without a wire version bump."""
import hashlib

import numpy as np
import pytest

import jax.numpy as jnp

from mpcium_tpu.ops import hash_suite as hs


def _rows(seed: bytes, n: int, width: int) -> np.ndarray:
    out = bytearray()
    ctr = 0
    while len(out) < n * width:
        out += hashlib.sha256(seed + ctr.to_bytes(4, "little")).digest()
        ctr += 1
    return np.frombuffer(bytes(out[: n * width]), np.uint8).reshape(n, width)


# ---------------------------------------------------------------------------
# SHA-256 / SHA-512 vs hashlib (FIPS 180-4)
# ---------------------------------------------------------------------------


def test_sha256_known_answer():
    msg = np.frombuffer(b"abc", np.uint8)
    assert bytes(np.asarray(hs.sha256(msg))) == bytes.fromhex(
        "ba7816bf8f01cfea414140de5dae2223b00361a396177a9cb410ff61f20015ad"
    )


def test_sha512_known_answer():
    msg = np.frombuffer(b"abc", np.uint8)
    assert bytes(np.asarray(hs.sha512(msg))) == bytes.fromhex(
        "ddaf35a193617abacc417349ae20413112e6fa4e89a97ea20a9eeee64b55d39a"
        "2192992a274fc1a836ba3c23a3feebbd454d4423643ce80e2a9ac94fa54ca49f"
    )


@pytest.mark.parametrize("length", [0, 1, 3, 55, 56, 63, 64, 100, 200])
def test_sha256_matches_hashlib(length):
    rows = _rows(b"s256|%d" % length, 8, max(length, 1))[:, :length]
    got = np.asarray(hs.sha256(jnp.asarray(rows)))
    for i in range(rows.shape[0]):
        assert bytes(got[i]) == hashlib.sha256(rows[i].tobytes()).digest()


@pytest.mark.parametrize(
    "length",
    # 111/112 straddle the single-block padding boundary, 128/240/300
    # force the multi-block loop, 0 is the degenerate message
    [0, 1, 3, 64, 111, 112, 127, 128, 240, 300],
)
def test_sha512_matches_hashlib(length):
    rows = _rows(b"s512|%d" % length, 8, max(length, 1))[:, :length]
    got = np.asarray(hs.sha512(jnp.asarray(rows)))
    for i in range(rows.shape[0]):
        assert bytes(got[i]) == hashlib.sha512(rows[i].tobytes()).digest()


def test_sha512_challenge_batch_shape():
    """The eddsa challenge shape: a (B, 96) batch (R‖A‖M with 32-byte
    messages) hashed as one dispatch, vs per-row hashlib."""
    rows = _rows(b"chal", 32, 96)
    got = np.asarray(hs.sha512(jnp.asarray(rows)))
    assert got.shape == (32, 64)
    for i in range(32):
        assert bytes(got[i]) == hashlib.sha512(rows[i].tobytes()).digest()


def test_sha512_bytes_single_digest():
    for msg in (b"", b"x", b"m" * 200):
        assert hs.sha512_bytes(msg) == hashlib.sha512(msg).digest()


# ---------------------------------------------------------------------------
# packed bit-transpose vs numpy packbits
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(8, 3), (16, 5), (24, 1), (128, 64), (256, 16)])
def test_transpose_matches_numpy(shape):
    R, C = shape
    packed = _rows(b"tr|%d|%d" % shape, R, C)
    bits = np.unpackbits(packed, axis=-1, bitorder="little")  # (R, 8C)
    want = np.packbits(bits.T, axis=-1, bitorder="little")  # (8C, R/8)
    got = np.asarray(hs.ot_transpose_device(jnp.asarray(packed)))
    assert got.shape == (8 * C, R // 8)
    assert np.array_equal(got, want)


def test_transpose_involution():
    packed = _rows(b"inv", 128, 16)
    once = hs.ot_transpose_device(jnp.asarray(packed))
    twice = np.asarray(hs.ot_transpose_device(once))
    assert np.array_equal(twice, packed)


def test_pack_unpack_bits_roundtrip():
    packed = _rows(b"pb", 4, 12)
    bits = np.asarray(hs.unpack_bits_core(jnp.asarray(packed)))
    assert np.array_equal(
        bits, np.unpackbits(packed, axis=-1, bitorder="little")
    )
    assert np.array_equal(
        np.asarray(hs.pack_bits_core(jnp.asarray(bits))), packed
    )


# ---------------------------------------------------------------------------
# OT kernels vs the host path in mta_ot
# ---------------------------------------------------------------------------


def test_prg_expand_matches_host_prg():
    from mpcium_tpu.protocol.ecdsa import mta_ot

    seeds = _rows(b"prg-seeds", 6, 32)
    tag = b"t-hs|v2|9"
    prefix = b"mpcium-ot-prg|" + tag
    for nblk, blk_off in ((1, 0), (3, 0), (4, 7)):
        want = mta_ot._prg(seeds, nblk * 32, tag, blk_off)
        got = np.asarray(hs.prg_expand_device(prefix, seeds, nblk, blk_off))
        assert np.array_equal(got, want), (nblk, blk_off)


def test_pad_hash_matches_host_rows():
    from mpcium_tpu.protocol.ecdsa.mta_ot import _hash_rows

    rows = _rows(b"pad-rows", 64, 16)
    prefix = b"mpcium-ot-pad|t-hs|v2|9|s1"
    m_off = 37
    idx = np.arange(m_off, m_off + 64, dtype=np.uint32).view(np.uint8)
    want = _hash_rows(prefix, np.concatenate([rows, idx.reshape(64, 4)], axis=1))
    got = np.asarray(
        hs.pad_hash_device(
            jnp.asarray(np.frombuffer(prefix, np.uint8)),
            jnp.asarray(rows),
            jnp.uint32(m_off),
        )
    )
    assert np.array_equal(got, want)


def test_le_bytes_helpers():
    x = jnp.asarray(np.array([0, 1, 0x1234, 0xDEADBEEF], np.uint32))
    le32 = np.asarray(hs.le32_bytes(x))
    assert np.array_equal(
        le32, np.array([0, 1, 0x1234, 0xDEADBEEF], np.uint32).view(np.uint8).reshape(4, 4)
    )
    le16 = np.asarray(hs.le16_bytes(jnp.asarray(np.array([0, 0x1234], np.uint32))))
    assert np.array_equal(
        le16, np.array([0, 0x1234], np.uint16).view(np.uint8).reshape(2, 2)
    )


# ---------------------------------------------------------------------------
# eddsa challenge: device vs hashlib, and the ops.sha256 delegation shim
# ---------------------------------------------------------------------------


def test_challenge_device_matches_hashlib():
    from mpcium_tpu.engine import eddsa_batch as eb

    R = _rows(b"R", 8, 32)
    A = _rows(b"A", 8, 32)
    M = _rows(b"M", 8, 32)
    got = np.asarray(eb.challenge_device(R, A, M))
    for i in range(8):
        want = hashlib.sha512(
            R[i].tobytes() + A[i].tobytes() + M[i].tobytes()
        ).digest()
        assert bytes(got[i]) == want


def test_challenge_hashes_paths_agree(monkeypatch):
    """challenge_hashes must produce the same bytes with the device path
    on and off, for equal-length and ragged batches."""
    from mpcium_tpu.engine import eddsa_batch as eb

    R = _rows(b"R2", 4, 32)
    A = _rows(b"A2", 4, 32)
    equal = [bytes(_rows(b"m%d" % i, 1, 32)[0]) for i in range(4)]
    ragged = [b"x" * (i + 1) for i in range(4)]
    for msgs in (equal, ragged):
        monkeypatch.setenv("MPCIUM_EDDSA_DEVICE_HASH", "1")
        dev = eb.challenge_hashes(R, A, msgs)
        monkeypatch.setenv("MPCIUM_EDDSA_DEVICE_HASH", "0")
        host = eb.challenge_hashes(R, A, msgs)
        assert np.array_equal(dev, host)
        for i, m in enumerate(msgs):
            want = hashlib.sha512(
                R[i].tobytes() + A[i].tobytes() + m
            ).digest()
            assert bytes(dev[i]) == want


def test_ops_sha256_shim_unchanged():
    from mpcium_tpu.ops.sha256 import sha256 as dev_sha256

    rows = _rows(b"shim", 4, 96)
    got = np.asarray(dev_sha256(jnp.asarray(rows)))
    for i in range(4):
        assert bytes(got[i]) == hashlib.sha256(rows[i].tobytes()).digest()


# ---------------------------------------------------------------------------
# SHA-512 over rows of different lengths (lengths are data, not a compile)
# ---------------------------------------------------------------------------


def _ragged(seed: bytes, lens, cap: int) -> np.ndarray:
    rows = np.zeros((len(lens), cap), np.uint8)
    for i, n in enumerate(lens):
        rows[i, :n] = _rows(seed + b"|%d" % i, 1, max(n, 1))[0, :n]
    return rows


def _assert_masked_matches_hashlib(rows, lens):
    got = np.asarray(hs.sha512_masked(
        jnp.asarray(rows), jnp.asarray(np.asarray(lens, np.int32))))
    for i, n in enumerate(lens):
        assert bytes(got[i]) == hashlib.sha512(
            rows[i, :n].tobytes()).digest(), (i, n)


def test_the_rung_ladder_holds_every_length_up_to_the_top():
    assert hs.SHA512_RUNGS == (1, 2, 4, 8, 16)
    caps = [blocks * 128 - 17 for blocks in hs.SHA512_RUNGS]
    assert caps == [111, 239, 495, 1007, 2031]
    for cap, nxt in zip(caps, caps[1:] + [None]):
        assert hs.sha512_rung_cap(cap) == cap
        assert hs.sha512_rung_cap(cap + 1) == nxt
        assert hs.sha512_masked_blocks(cap) * 128 == cap + 17
    assert hs.sha512_rung_cap(0) == 111
    # R ‖ A ‖ M: a 32-byte digest is the 1-block rung, a Solana message
    # at the packet's limit the 16-block one, 1,967 bytes the longest
    assert hs.sha512_rung_cap(64 + 32) == 111
    assert hs.sha512_rung_cap(64 + 1167) == 2031
    assert hs.sha512_rung_cap(64 + 1967) == 2031
    assert hs.sha512_rung_cap(64 + 1968) is None


@pytest.mark.parametrize("blocks", hs.SHA512_RUNGS)
def test_sha512_masked_matches_hashlib_on_ragged_rows(blocks):
    """Bit for bit against ``hashlib``, a row at a time: seeded ragged
    rows at every rung, every length whose padding lands on a block's
    edge (total lengths at 110, 111, 112, 127, 128, 129 modulo 128), the
    empty row and the full one, lanes of one batch ending in different
    blocks."""
    cap = blocks * 128 - 17
    edges = sorted({n for k in range(blocks + 1)
                    for n in (128 * k + r for r in (-18, -17, -16, -1, 0, 1))
                    if 0 <= n <= cap})
    rng = np.random.default_rng(blocks)
    drawn = [int(n) for n in rng.integers(0, cap + 1, size=8)]
    lens = [0, cap] + edges + drawn
    last_blocks = {(n + 16) // 128 for n in lens}
    assert last_blocks == set(range(blocks))  # every block ends some lane
    assert {n % 128 for n in edges} >= (
        {110, 111} if blocks == 1 else {110, 111, 112, 127, 0, 1})
    _assert_masked_matches_hashlib(
        _ragged(b"masked|%d" % blocks, lens, cap), lens)


def test_sha512_masked_ignores_what_stands_past_a_lanes_length():
    lens = [0, 5, 96, 111]
    rows = _rows(b"dirty", 4, 111)  # nothing zero-filled
    _assert_masked_matches_hashlib(rows, lens)


def test_sha512_masked_compiles_once_for_any_lengths():
    """Two batches of one width and other lengths: one compile (the
    lengths are data); another rung is another program."""
    a = _ragged(b"once-a", [3, 200, 239, 17], 239)
    b = _ragged(b"once-b", [239, 0, 64, 128], 239)
    _assert_masked_matches_hashlib(a, [3, 200, 239, 17])
    before = hs.sha512_masked._cache_size()
    _assert_masked_matches_hashlib(b, [239, 0, 64, 128])
    assert hs.sha512_masked._cache_size() == before
    _assert_masked_matches_hashlib(_ragged(b"once-c", [9], 495), [9])
    assert hs.sha512_masked._cache_size() == before + 1


def test_sha512_masked_agrees_with_the_fixed_kernel_on_equal_rows():
    rows = _rows(b"equal", 8, 96)
    want = np.asarray(hs.sha512(jnp.asarray(rows)))
    padded = np.zeros((8, 111), np.uint8)
    padded[:, :96] = rows
    got = np.asarray(hs.sha512_masked(
        jnp.asarray(padded), jnp.full((8,), 96, jnp.int32)))
    assert np.array_equal(got, want)


def test_challenge_device_takes_lengths_as_data():
    from mpcium_tpu.engine import eddsa_batch as eb

    # 64 + L at 110, 111, 112, 127, 128 and 129 modulo 128 among them
    sizes = (150, 215, 1167, 400, 46, 47, 48, 63, 64, 65, 1070, 1071, 1072)
    R = _rows(b"R3", len(sizes), 32)
    A = _rows(b"A3", len(sizes), 32)
    msgs = [bytes(_rows(b"msg|%d" % n, 1, n)[0]) for n in sizes]
    M, lens = eb.pack_messages(msgs)
    assert M.shape == (len(sizes), 2031 - 64) and list(lens) == [len(m) for m in msgs]
    assert all(bytes(M[i, :n]) == msgs[i] and not M[i, n:].any()
               for i, n in enumerate(lens))
    got = np.asarray(eb.challenge_device(R, A, M, lens))
    for i, m in enumerate(msgs):
        assert bytes(got[i]) == hashlib.sha512(
            R[i].tobytes() + A[i].tobytes() + m).digest()
    # the rung follows the longest message: 47 bytes is the last length
    # of the 1-block rung, 1,967 the last of the top one, then the host
    assert eb.pack_messages([b"x" * 47, b""])[0].shape == (2, 47)
    assert eb.pack_messages([b"x" * 48])[0].shape == (1, 239 - 64)
    assert eb.pack_messages([b"x" * 1967])[0].shape == (1, 1967)
    assert eb.pack_messages([b"x" * 1968, b"y"]) is None
