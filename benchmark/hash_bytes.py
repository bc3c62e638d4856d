"""Bytes the Ed25519 challenge hash has to move, from shapes: the masked
SHA-512 (``mpcium_tpu/ops/hash_suite.py`` ``sha512_masked``) reads every
lane's row (``cap`` bytes: R, A and the message zero-filled to the rung's
width) and its length (4), and writes its digest (64). Each of the q
signing parties hashes every lane of the wave. A lower bound on what the
kernel moves (its schedule and state are kept in between), so the share of
the HBM peak it gives bounds the hash's time from below and is no target.
"""
from __future__ import annotations

SHA512_BLOCK = 128
SHA512_TAIL = 17  # the 0x80 byte and the 16-byte bit length
RUNGS = (1, 2, 4, 8, 16)  # blocks a lane is padded to
PROGRAM = "jit_sha512_masked"  # the hash's name in the device trace


def rung_cap(longest_message: int) -> int:
    """Row width (R ‖ A ‖ M, zero-filled) of the smallest rung that holds
    a message of ``longest_message`` bytes with SHA-512's padding."""
    for blocks in RUNGS:
        if 64 + longest_message + SHA512_TAIL <= blocks * SHA512_BLOCK:
            return blocks * SHA512_BLOCK - SHA512_TAIL
    raise ValueError(f"no rung holds a {longest_message}-byte message")


def per_wave(wave: int, q: int, longest_message: int) -> int:
    """Bytes read and written by the challenge hashes of one wave."""
    return q * wave * (rung_cap(longest_message) + 4 + 64)
