"""The wallet population, made from the seed by the benchmark itself.

Each wallet is an RFC 8032 key made by OpenSSL from 32 seeded bytes: the
public key is OpenSSL's, the secret scalar is the clamped low half of
SHA-512(seed bytes), reduced mod l, and it is Shamir-shared over the
committee with seeded coefficients. The program only ever sees the shares
(as the ``KeygenShare`` records its nodes store) — the public keys the
check verifies under never pass through the program's curve code, and
4096 wallets take well under a second where the program's own test dealer
(``dealer_keygen_batch``, python-int scalar multiplications) takes ~15 s.
"""
from __future__ import annotations

import hashlib
import random
from typing import Dict, List, Sequence, Tuple

from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey,
)

# order of the edwards25519 prime-order subgroup (RFC 8032 section 5.1)
ED_L = 2**252 + 27742317777372353535851937790883648493


def _secret_scalar(seed32: bytes) -> int:
    h = bytearray(hashlib.sha512(seed32).digest()[:32])
    h[0] &= 248
    h[31] &= 127
    h[31] |= 64
    return int.from_bytes(h, "little") % ED_L


def shamir_shares(secret: int, threshold: int, xs: Sequence[int],
                  rng: random.Random) -> Dict[int, int]:
    """Degree-``threshold`` polynomial with f(0) = secret, evaluated at
    ``xs`` (threshold t means t+1 shares reconstruct)."""
    coeffs = [secret] + [rng.randrange(1, ED_L) for _ in range(threshold)]
    out = {}
    for x in xs:
        acc = 0
        for c in reversed(coeffs):
            acc = (acc * x + c) % ED_L
        out[x] = acc
    return out


def make_wallets(n_wallets: int, party_xs: Dict[str, int], threshold: int,
                 rng: random.Random) -> Tuple[List[bytes], Dict[str, List[int]]]:
    """-> (public keys, {party id: [share of wallet w]})."""
    pubkeys: List[bytes] = []
    shares: Dict[str, List[int]] = {pid: [] for pid in party_xs}
    xs = list(party_xs.values())
    for _ in range(n_wallets):
        seed32 = rng.randbytes(32)
        sk = Ed25519PrivateKey.from_private_bytes(seed32)
        pubkeys.append(sk.public_key().public_bytes_raw())
        by_x = shamir_shares(_secret_scalar(seed32), threshold, xs, rng)
        for pid, x in party_xs.items():
            shares[pid].append(by_x[x])
    return pubkeys, shares
