"""Traffic of kind ``closed_waves``: one client, closed loop, whole waves.

A wave is ``wave_size`` sign requests, each for a distinct wallet, all in
flight at once; the next wave goes out when the last result of the
previous one is in — what a custodian's payout or sweep batch does. The
wallets are walked in a seeded permutation of the population, the digests
are seeded bytes, so a seed fixes the inputs; every seed sends waves of
the same size, so the work per wave is the same for all of them.

A new wave starts only while the time left in the window is at least the
previous wave's duration, so a run overruns its window by a fraction of a
wave at most and every submitted request reaches its terminal outcome
inside the run.
"""
from __future__ import annotations

import random
import time
from typing import Callable, List, Optional


def _wave_size(params: dict, served) -> int:
    size = params["wave_size"]
    return served.wave_size if size == "batch_max_batch" else int(size)


def _wallet_walk(n_wallets: int, wave: int, rng: random.Random):
    """Endless seeded walk over the population in chunks of ``wave``
    distinct wallets: a fresh permutation whenever one is used up."""
    if wave > n_wallets:
        raise ValueError(f"a wave of {wave} distinct wallets needs a "
                         f"population of at least {wave}, not {n_wallets}")
    order: List[int] = []
    while True:
        if len(order) < wave:
            order = list(range(n_wallets))
            rng.shuffle(order)
        chunk, order = order[:wave], order[wave:]
        yield chunk


def drive(served, params: dict, seed: int, seconds: float,
          on_wave: Optional[Callable] = None,
          before_wave: Optional[Callable] = None) -> dict:
    """Run the unmeasured waves, then measured waves for ``seconds``.
    -> {"waves": [Wave...], "window_start_ns", "window_end_ns"}.
    ``before_wave(index, measured)`` / ``on_wave(wave)`` are the
    harness's hooks (tracing); they run between waves, outside any wave's
    own clock readings."""
    rng = random.Random(seed ^ 0x5EED_7AFF)
    size = _wave_size(params, served)
    walk = _wallet_walk(served.n_wallets, size, rng)
    timeout_s = float(params["wave_timeout_s"])
    waves = []

    def one(index: int, measured: bool):
        wallets = next(walk)
        digests = [rng.randbytes(served.digest_bytes) for _ in wallets]
        if before_wave is not None:
            before_wave(index, measured)
        wave = served.run_wave(index, measured, wallets, digests, params,
                               timeout_s)
        waves.append(wave)
        if on_wave is not None:
            on_wave(wave)
        return wave

    index = 0
    for _ in range(int(params["unmeasured_waves"])):
        one(index, False)
        index += 1
    window_start = time.monotonic_ns()
    budget_ns = int(seconds * 1e9)
    while True:
        wave = one(index, True)
        index += 1
        if any(r.done_ns is None for r in wave.requests):
            break  # a wave timed out: the run is already lost
        left = budget_ns - (time.monotonic_ns() - window_start)
        if left < wave.done_ns - wave.t0_ns:
            break
    return {"waves": waves, "window_start_ns": window_start,
            "window_end_ns": time.monotonic_ns()}
