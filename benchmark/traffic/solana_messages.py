"""Traffic of kind ``solana_messages``: the closed loop of
``closed_waves.py`` (one client, whole waves of distinct wallets, the next
wave when the last result is in) whose requests carry what an Ed25519
signer of a Solana transaction really signs: the serialized legacy
MESSAGE, 150 to 1,167 bytes, of different lengths inside one wave
(``solana_layout.py`` beside this file builds them). RFC 8032 has no
prehash, so the bytes go to the signing parties as they are.

The loop is the one beside this file, loaded and not copied: it is shown
a ``served`` whose digests are 32 bytes, and each 32-byte draw becomes one
message: the draw is the message's recent blockhash and the seed of its
kind and sizes, the wallet's public key its fee payer. So for a seed this
kind walks the very wallets ``closed_waves`` walks, in its order. A
wave's FIRST request is always a program call at the longest length: one
request in a wave, and it pins the wave to the hash's top rung at any
wave size, so a rehearsal's waves of 8 meet the rule the chip's waves of
1,024 meet (a wave with no long message would otherwise land on a lower
rung, which the warm batch did not compile).

After the window it holds the run to what "the challenge was hashed on
the device" and "these bytes were sent" mean: no row was hashed on the
host (``party.eddsa.host_hash_rows_total`` stood still), and every node
that took requests in counted exactly the bytes sent
(``intake.tx_bytes_total``). A run that breaks either prints no result.

A program whose SHA-512 cannot take rows of different lengths is refused
when this file is LOADED: the cell then fails at ``harness.Cell(...)``, in
seconds, before a wallet is made.
"""
from __future__ import annotations

import os
from typing import Callable, Dict, List, Optional

from benchmark import harness
from mpcium_tpu.ops import hash_suite

if not hasattr(hash_suite, "sha512_masked"):
    raise RuntimeError(
        "traffic of kind solana_messages needs a SHA-512 over rows of "
        "different lengths (mpcium_tpu.ops.hash_suite.sha512_masked: this "
        "program has none): its ragged batches would be hashed a row at a "
        "time on the host, which no cell measures")

_HERE = os.path.dirname(os.path.abspath(__file__))
_closed_waves = harness._load_module(os.path.join(_HERE, "closed_waves.py"))
layout = harness._load_module(os.path.join(_HERE, "solana_layout.py"))

HOST_ROWS = "party.eddsa.host_hash_rows_total"
TX_BYTES = "intake.tx_bytes_total"


def wave_messages(pubkeys: List[bytes], wallets: List[int],
                  draws: List[bytes], mix: dict) -> List[bytes]:
    """The wave's messages: one a (wallet, 32-byte draw), the first a
    program call at the longest length."""
    return [layout.draw(pubkeys[w], d, mix, at_cap=(i == 0))
            for i, (w, d) in enumerate(zip(wallets, draws))]


class _AsDigests:
    """``served`` as ``closed_waves`` is shown it: 32-byte digests, and a
    ``run_wave`` that sends each draw's message in its place."""

    digest_bytes = 32

    def __init__(self, served, mix: dict):
        self._served = served
        self._mix = mix
        self.sent_bytes = 0

    def __getattr__(self, name):
        return getattr(self._served, name)

    def run_wave(self, index, measured, wallets, digests, params, timeout_s):
        messages = wave_messages(self._served.pubkeys, wallets, digests,
                                 self._mix)
        self.sent_bytes += sum(len(m) for m in messages)
        return self._served.run_wave(index, measured, wallets, messages,
                                     params, timeout_s)


def _intake(served) -> Dict[str, tuple]:
    """node id -> (requests it took in, payload bytes it counted)."""
    return {nid: (snap["histograms"].get("intake.handle_s", {})
                  .get("count", 0), snap["counters"].get(TX_BYTES, 0.0))
            for nid, snap in served.metrics_snapshot().items()}


def drive(served, params: dict, seed: int, seconds: float,
          on_wave: Optional[Callable] = None,
          before_wave: Optional[Callable] = None) -> dict:
    shown = _AsDigests(served, params["messages"])
    host_rows = served.counter_total(HOST_ROWS)
    at_start = _intake(served)
    driven = _closed_waves.drive(shown, params, seed, seconds,
                                 on_wave=on_wave, before_wave=before_wave)
    hashed_on_host = served.counter_total(HOST_ROWS) - host_rows
    if hashed_on_host:
        raise RuntimeError(
            f"{int(hashed_on_host)} challenges were hashed on the host "
            f"({HOST_ROWS}): the cell measures the device's hash")
    took = {nid: (n - at_start[nid][0], b - at_start[nid][1])
            for nid, (n, b) in _intake(served).items()}
    wrong = {nid: t for nid, t in took.items()
             if t[0] and t[1] != shown.sent_bytes}
    if wrong or not any(t[0] for t in took.values()):
        raise RuntimeError(
            f"{shown.sent_bytes} payload bytes were sent; nodes that took "
            f"requests in counted otherwise (requests, {TX_BYTES}): "
            f"{wrong or took}")
    return driven
