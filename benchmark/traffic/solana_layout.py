"""Legacy Solana transaction messages, built from a seed by the layout of
Solana's documentation ("Transactions"): what an Ed25519 signer of a Solana
transaction signs is the serialized MESSAGE, as it is, with no prehash.

    header            3 bytes: required signatures, read-only signed
                      accounts, read-only unsigned accounts
    account keys      compact-u16 count, then 32 bytes each (the fee
                      payer, who signs, first)
    recent blockhash  32 bytes
    instructions      compact-u16 count, then each: program id index (u8),
                      compact-u16 count of account indices (u8 each),
                      compact-u16 length of the data, the data

A legacy message starts with its count of required signatures (1 here);
a versioned (v0) message would start with 0x80 and end with address-table
lookups, and is not built here. A message fits a packet of 1,232 bytes
beside its signatures: with one signature (1 count byte + 64), 1,167.

Three kinds, each a function of the signer's key, the recent blockhash
and a ``random.Random``; nothing is downloaded and nothing here imports
the program.
"""
from __future__ import annotations

import random
import struct
from typing import Sequence, Tuple

PACKET_BYTES = 1232
MESSAGE_CAP = PACKET_BYTES - 1 - 64  # one signature and its count: 1,167

_B58 = "123456789ABCDEFGHJKLMNPQRSTUVWXYZabcdefghijkmnopqrstuvwxyz"


def _b58_key(text: str) -> bytes:
    n = 0
    for ch in text:
        n = n * 58 + _B58.index(ch)
    return n.to_bytes(32, "big")


SYSTEM_PROGRAM = _b58_key("11111111111111111111111111111111")
TOKEN_PROGRAM = _b58_key("TokenkegQfeZyiNwAJbNbGKPFXCWuBvf9Ss623VQ5DA")
COMPUTE_BUDGET_PROGRAM = _b58_key(
    "ComputeBudget111111111111111111111111111111")


def compact_u16(n: int) -> bytes:
    """Solana's short vector length: 7 bits a byte, low first."""
    if not 0 <= n < 1 << 16:
        raise ValueError(f"no compact-u16 holds {n}")
    out = bytearray()
    while True:
        low = n & 0x7F
        n >>= 7
        out.append(low | (0x80 if n else 0))
        if not n:
            return bytes(out)


def instruction(program_index: int, accounts: Sequence[int],
                data: bytes) -> bytes:
    return (bytes([program_index]) + compact_u16(len(accounts))
            + bytes(accounts) + compact_u16(len(data)) + data)


def message(header: Tuple[int, int, int], keys: Sequence[bytes],
            blockhash: bytes, instructions: Sequence[bytes]) -> bytes:
    if len(blockhash) != 32 or any(len(k) != 32 for k in keys):
        raise ValueError("keys and the blockhash are 32 bytes each")
    return (bytes(header) + compact_u16(len(keys)) + b"".join(keys)
            + blockhash + compact_u16(len(instructions))
            + b"".join(instructions))


def transfer(signer: bytes, blockhash: bytes, rng: random.Random) -> bytes:
    """System ``Transfer``: 3 accounts, 12 data bytes: 150 bytes."""
    data = struct.pack("<IQ", 2, rng.randrange(1, 10 ** 12))
    return message((1, 0, 1), [signer, rng.randbytes(32), SYSTEM_PROGRAM],
                   blockhash, [instruction(2, [0, 1], data)])


def transfer_checked(signer: bytes, blockhash: bytes,
                     rng: random.Random) -> bytes:
    """SPL-Token ``TransferChecked``: 5 accounts (owner, source,
    destination, mint, the token program), 10 data bytes, the
    instruction naming source, mint, destination and owner: 214 bytes."""
    source, destination, mint = (rng.randbytes(32) for _ in range(3))
    data = struct.pack("<BQB", 12, rng.randrange(1, 10 ** 12),
                       rng.choice((6, 9)))
    return message((1, 0, 2),
                   [signer, source, destination, mint, TOKEN_PROGRAM],
                   blockhash, [instruction(4, [1, 3, 2, 0], data)])


def _call_fixed_bytes(n_accounts: int, named: int) -> int:
    """A program call's bytes apart from the last instruction's data and
    that data's length prefix."""
    budget = len(instruction(0, [], bytes(5)))
    return (3 + len(compact_u16(n_accounts)) + 32 * n_accounts + 32 + 1
            + budget + 1 + len(compact_u16(named)) + named)


def program_call(signer: bytes, blockhash: bytes, rng: random.Random,
                 length: int, accounts: int) -> bytes:
    """A call into a program, exactly ``length`` bytes long: the signer,
    other accounts, the compute-budget program and the called program
    (``accounts`` keys in all, fewer where ``length`` leaves no room),
    a ``SetComputeUnitLimit`` and the call itself, which names every
    account but the two programs and whose data fills the rest."""
    n = accounts
    while n > 3 and _call_fixed_bytes(n, n - 2) + 1 > length:
        n -= 1
    named = n - 2
    rest = length - _call_fixed_bytes(n, named)
    if rest == 129:  # no length prefix fits: 1 + 127 < 129 < 2 + 128
        named -= 1
        rest += 1
    data_len = rest - (1 if rest <= 128 else 2)
    if data_len < 0:
        raise ValueError(f"no program call is {length} bytes long")
    keys = ([signer] + [rng.randbytes(32) for _ in range(n - 3)]
            + [COMPUTE_BUDGET_PROGRAM, rng.randbytes(32)])
    limit = struct.pack("<BI", 2, rng.randrange(200_000, 1_400_001))
    built = message(
        (1, 0, 2 + (n - 3) // 2), keys, blockhash,
        [instruction(n - 2, [], limit),
         instruction(n - 1, list(range(named)), rng.randbytes(data_len))])
    assert len(built) == length, (len(built), length)
    return built


def draw(signer: bytes, blockhash: bytes, mix: dict,
         at_cap: bool = False) -> bytes:
    """One message for ``signer``: ``blockhash`` is its recent blockhash
    and the seed of its kind and sizes. ``mix``: the shares of the three
    kinds and the program call's ranges (a traffic file's ``messages``).
    ``at_cap``: a program call at the longest length, whatever is drawn."""
    rng = random.Random(blockhash)
    shares = mix["shares"]
    lo, hi = mix["program_call_bytes"]
    few, many = mix["program_call_accounts"]
    if at_cap:
        return program_call(signer, blockhash, rng, hi, many)
    u = rng.random()
    if u < shares["transfer"]:
        return transfer(signer, blockhash, rng)
    if u < shares["transfer"] + shares["transfer_checked"]:
        return transfer_checked(signer, blockhash, rng)
    return program_call(signer, blockhash, rng, rng.randint(lo, hi),
                        rng.randint(few, many))

