"""The plain reference: RFC 8032 verification by OpenSSL (``cryptography``).

Signing nonces are the program's CSPRNG, so signature bytes cannot be
compared with anything; what a signature *means* can: it verifies under
the wallet's public key, which this benchmark derived itself from the seed
with OpenSSL (wallets.py) — nothing here is the program's code or data.
"""
from __future__ import annotations

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PublicKey,
)


def verifies(public_key: bytes, message: bytes, signature: bytes) -> bool:
    if len(signature) != 64 or len(public_key) != 32:
        return False
    try:
        Ed25519PublicKey.from_public_bytes(public_key).verify(
            signature, message)
    except (InvalidSignature, ValueError):
        return False
    return True


def count_invalid(rows) -> int:
    """``rows``: (public_key, message, signature) triples."""
    return sum(0 if verifies(pk, m, sig) else 1 for pk, m, sig in rows)
