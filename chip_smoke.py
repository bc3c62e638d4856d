#!/usr/bin/env python
"""Chip smoke: the served Ed25519 2-of-3 signing path, once, on the chip.

    python chip_smoke.py                # one chip (what the driver runs)
    python chip_smoke.py --four-chips   # session axis over 4 chips vs 1

Drives client SDK -> loopback transport -> batch scheduler -> session ->
BatchedEDDSASigningParty -> engine/eddsa_batch in ONE process (a chip
belongs to one process; nothing here starts a child that needs it).

Built to the clock. Every batch width is a compile and one shape costs
minutes cold, so the traffic is shaped to need exactly one: waves of
exactly WAVE sign requests, each for a distinct wallet, submitted back to
back with the whole wave in flight (the fabric's queue workers are raised
above the wave, because the signing bridge holds one per in-flight sign),
so the leader fires the moment the last request arrives — one manifest of
WAVE, never chunks. That one shape is compiled once, in the main thread,
before any node thread can meet it cold.

Checks (any miss => non-zero exit, no `"ok": true` line): platform is
tpu; exactly one `party.eddsa` compile shape; the post-warm waves compile
nothing; every batch full; books closed (submitted == succeeded, nothing
shed, failed or pending); every measured signature verifies under its
wallet's public key with `cryptography` (OpenSSL), which is not this
repo's code. The printed seconds are smoke readings on a shared host
clock — not benchmark numbers, never to be quoted as rates.

Output: one JSON object per line; the LAST line is
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import sys
import threading
import time

N_NODES = 3
THRESHOLD = 1  # 2-of-3
# every READY participant signs (node._ready_quorum), so with all three
# nodes up the served quorum — and the compile shape's q — is 3
QUORUM = N_NODES
N_WALLETS = 4096  # dealer-dealt, in every node's encrypted share store
WAVE = 1024  # = config.batch_max_batch's default: one full manifest
MEASURED_WAVES = 3
SEED = 1337
# the window only exists so a partial wave never fires first; every
# timeout outlasts a cold compile should the warm-up ever miss
WINDOW_S = 60.0
WAVE_TIMEOUT_S = 600.0


class SmokeFailure(Exception):
    """A phase of the smoke failed its check."""


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def accelerator(min_count: int = 1) -> dict:
    """The device as JAX reports it; anything but a TPU is a failure —
    this script never carries on on the CPU."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise SmokeFailure(f"no accelerator: JAX found {dev}")
    if dev["count"] < min_count:
        raise SmokeFailure(f"need {min_count} chips: JAX found {dev}")
    return dev


def device_memory(dev) -> dict:
    return dev.memory_stats() or {}


class CompileCounter:
    """Counts what XLA was asked to compile, from JAX's own monitoring
    events: every backend compile request, and the persistent cache's
    hits and misses among them."""

    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self) -> None:
        from jax import monitoring

        self._lock = threading.Lock()
        self.requests = self.hits = self.misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == self._BACKEND:
            with self._lock:
                self.requests += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event in (self._HIT, self._MISS):
            with self._lock:
                if event == self._HIT:
                    self.hits += 1
                else:
                    self.misses += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"requests": self.requests, "cache_hits": self.hits,
                    "cache_misses": self.misses}


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


class Served:
    """The cluster under test plus the client-side ledger of one run."""

    def __init__(self, counter: CompileCounter):
        from mpcium_tpu.cluster import LocalCluster, load_test_preparams
        from mpcium_tpu.engine import eddsa_batch as eb
        from mpcium_tpu.engine.pipeline import resolve_cohorts

        self.counter = counter
        self.cohorts = resolve_cohorts(WAVE)
        t0 = time.monotonic()
        self.cluster = LocalCluster(
            n_nodes=N_NODES,
            threshold=THRESHOLD,
            preparams=load_test_preparams(),
            batch_signing=True,
            batch_window_s=WINDOW_S,
            reply_timeout_s=WAVE_TIMEOUT_S,
            batch_max_batch=WAVE,
            batch_manifest_timeout_s=WAVE_TIMEOUT_S,
            loopback_workers=WAVE + 64,
        )
        ids = self.cluster.node_ids
        shares = eb.dealer_keygen_batch(N_WALLETS, ids, threshold=THRESHOLD)
        self.wallets = [f"smokew{w}" for w in range(N_WALLETS)]
        self.pubkeys = {
            wid: shares[0][w].public_key for w, wid in enumerate(self.wallets)
        }
        for w, wid in enumerate(self.wallets):
            for i, nid in enumerate(ids):
                self.cluster.nodes[nid].save_share(shares[i][w], wid)
        self.setup_s = time.monotonic() - t0
        self._rng = random.Random(SEED)
        self._lock = threading.Lock()
        self._results: dict = {}
        self._wave_done = threading.Event()
        self._sub = self.cluster.client.on_sign_result(self._on_result)
        self.submitted = self.succeeded = 0
        self.signed: list = []  # (wallet_id, digest, signature) measured

    def close(self) -> None:
        self._sub.unsubscribe()
        self.cluster.close()

    # -- the warm compile ---------------------------------------------------

    def warm(self) -> dict:
        """One party-level batch at the wave's shape with throwaway
        shares, in THIS (the main) thread: the QUORUM parties run the
        whole 3-round protocol through the synchronous in-process runner
        (what warm/prewarm.py does for party.ecdsa), so every kernel the
        node threads will call is compiled before they can meet it."""
        from mpcium_tpu.engine import eddsa_batch as eb
        from mpcium_tpu.protocol.eddsa.batch_signing import (
            BatchedEDDSASigningParty,
        )
        from mpcium_tpu.protocol.runner import run_protocol

        ids = self.cluster.node_ids[:QUORUM]
        before = self.counter.snapshot()
        t0 = time.monotonic()
        shares = eb.dealer_keygen_batch(WAVE, ids, threshold=THRESHOLD)
        digests = [bytes([i % 256]) * 32 for i in range(WAVE)]
        parties = {
            pid: BatchedEDDSASigningParty(
                "smoke-warm", pid, ids, shares[i], digests,
                cohorts=self.cohorts,
            )
            for i, pid in enumerate(ids)
        }
        run_protocol(parties)
        for pid, p in parties.items():
            if not bool(p.result["ok"].all()):
                raise SmokeFailure(f"warm batch failed verification at {pid}")
        return {"seconds": time.monotonic() - t0,
                **_delta(self.counter.snapshot(), before)}

    # -- one wave -----------------------------------------------------------

    def _on_result(self, ev) -> None:
        with self._lock:
            self._results[ev.tx_id] = ev
            if len(self._results) >= WAVE:
                self._wave_done.set()

    def wave(self, index: int, measured: bool) -> dict:
        """WAVE sign requests for WAVE distinct wallets, back to back;
        ends when the last result event arrives (host clock)."""
        from mpcium_tpu import wire

        start = (index * WAVE) % N_WALLETS
        wallets = self.wallets[start:start + WAVE]
        if len(set(wallets)) != WAVE:
            raise SmokeFailure("a wave needs WAVE distinct wallets")
        digests = [self._rng.randbytes(32) for _ in wallets]
        tx_ids = [f"smoke-w{index}-{i}" for i in range(WAVE)]
        with self._lock:
            self._results.clear()
            self._wave_done.clear()
        before = self.counter.snapshot()
        fired0 = self._counter_total("scheduler.batches_fired_total")
        t0 = time.monotonic()
        for wid, digest, tx_id in zip(wallets, digests, tx_ids):
            self.cluster.client.sign_transaction(wire.SignTxMessage(
                key_type="ed25519",
                wallet_id=wid,
                network_internal_code="sol",
                tx_id=tx_id,
                tx=digest,
                deadline_ms=int(WAVE_TIMEOUT_S * 1000),
                priority=wire.PRIORITY_BULK,
            ))
        self.submitted += WAVE
        t_submitted = time.monotonic()
        finished = self._wave_done.wait(WAVE_TIMEOUT_S)
        seconds = time.monotonic() - t0
        with self._lock:
            results = dict(self._results)
        ok = [ev for ev in results.values()
              if ev.result_type == wire.RESULT_SUCCESS]
        self.succeeded += len(ok)
        report = {
            "wave": index,
            "measured": measured,
            "size": WAVE,
            "seconds": seconds,
            "submit_seconds": t_submitted - t0,
            "succeeded": len(ok),
            "failed": len(results) - len(ok),
            "pending": WAVE - len(results),
            "batches_fired":
                self._counter_total("scheduler.batches_fired_total") - fired0,
            "compiles": _delta(self.counter.snapshot(), before),
        }
        emit(phase="wave", **report)
        if not finished or len(ok) != WAVE:
            sample = [ev.error_reason for ev in results.values()
                      if ev.result_type != wire.RESULT_SUCCESS][:3]
            raise SmokeFailure(f"wave {index} did not close: {report} "
                               f"{sample}")
        if report["batches_fired"] != 1:
            raise SmokeFailure(
                f"wave {index} fired {report['batches_fired']} manifests, "
                f"not one of {WAVE}")
        if report["compiles"]["requests"] != 0:
            raise SmokeFailure(
                f"wave {index} compiled after the warm-up: "
                f"{report['compiles']}")
        if measured:
            for wid, digest, tx_id in zip(wallets, digests, tx_ids):
                self.signed.append(
                    (wid, digest, bytes.fromhex(results[tx_id].signature)))
        return report

    # -- after the waves ----------------------------------------------------

    def _counter_total(self, name: str) -> float:
        snap = self.cluster.metrics_snapshot()
        return sum(s["counters"].get(name, 0.0) for s in snap.values())

    def verify_signatures(self) -> int:
        """Every measured signature under its wallet's public key, by
        `cryptography` (OpenSSL) — raises on the first miss."""
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PublicKey,
        )

        for wid, digest, sig in self.signed:
            Ed25519PublicKey.from_public_bytes(self.pubkeys[wid]).verify(
                sig, digest)
        return len(self.signed)

    def close_books(self, waves: int) -> dict:
        from mpcium_tpu.perf import compile_watch

        snap = self.cluster.metrics_snapshot()
        fills = [s["histograms"].get("scheduler.batch_fill_ratio", {})
                 for s in snap.values()]
        fills = [f for f in fills if f.get("count")]
        shapes = sorted({e["shape"] for e in compile_watch.entries()
                         if e["engine"] == "party.eddsa"})
        books = {
            "submitted": self.submitted,
            "succeeded": self.succeeded,
            "shed": self._counter_total("scheduler.shed_total"),
            "fallbacks": self._counter_total("scheduler.fallback_total"),
            "batches_fired":
                self._counter_total("scheduler.batches_fired_total"),
            "batch_fill_ratio_min": min(f["min"] for f in fills)
            if fills else None,
            "batch_fill_ratio_max": max(f["max"] for f in fills)
            if fills else None,
            "party_eddsa_shapes": shapes,
        }
        emit(phase="books", **books)
        if books["submitted"] != books["succeeded"]:
            raise SmokeFailure(f"books do not close: {books}")
        if books["shed"] or books["fallbacks"]:
            raise SmokeFailure(f"requests shed or fell back: {books}")
        if books["batches_fired"] != waves:
            raise SmokeFailure(f"expected {waves} manifests: {books}")
        if (books["batch_fill_ratio_min"] != 1.0
                or books["batch_fill_ratio_max"] != 1.0):
            raise SmokeFailure(f"a batch was not full: {books}")
        if shapes != [f"B{WAVE}|q{QUORUM}"]:
            raise SmokeFailure(
                f"expected exactly one party.eddsa shape, got {shapes}")
        return books


def served_run(counter: CompileCounter, label: str) -> dict:
    """Set-up, warm compile, one unmeasured wave, MEASURED_WAVES measured
    waves, every check. Returns the wave seconds."""
    from mpcium_tpu import native

    served = Served(counter)
    try:
        emit(phase="setup", run=label, seconds=served.setup_s,
             nodes=N_NODES, threshold=THRESHOLD, wallets=N_WALLETS,
             quorum=QUORUM, wave=WAVE, cohorts=served.cohorts,
             engine_width=WAVE // served.cohorts,
             native_available=native.available())
        emit(phase="warm_compile", run=label, **served.warm())
        served.wave(0, measured=False)
        waves = [served.wave(i, measured=True)
                 for i in range(1, MEASURED_WAVES + 1)]
        n = served.verify_signatures()
        emit(phase="verify", run=label, verifier="cryptography",
             signatures=n, all_valid=True)
        if n != MEASURED_WAVES * WAVE:
            raise SmokeFailure(f"verified {n} signatures, expected "
                               f"{MEASURED_WAVES * WAVE}")
        served.close_books(MEASURED_WAVES + 1)
        return {"run": label, "wave_seconds": [w["seconds"] for w in waves]}
    finally:
        served.close()


def four_chips(counter: CompileCounter) -> None:
    """The same waves with the session axis armed over four devices, then
    disarmed on one device, in this one process."""
    import jax

    from mpcium_tpu.engine import eddsa_batch as eb
    from mpcium_tpu.engine import sharded

    device_sets: list = []
    plain_to_dev = eb.to_dev

    def observed_to_dev(x, axis: int = 0):
        out = plain_to_dev(x, axis)
        device_sets.append(len(out.sharding.device_set))
        return out

    eb.to_dev = observed_to_dev
    try:
        if sharded.arm_session_axis(4) is None:
            raise SmokeFailure("session axis did not arm over four devices")
        armed = served_run(counter, "session-axis-4")
        memory = [device_memory(d) for d in jax.devices()[:4]]
        placed = {"tensors": len(device_sets),
                  "device_set_sizes": sorted(set(device_sets)),
                  "unsharded_placements": eb.unsharded_placements(),
                  "peak_bytes_in_use":
                      [m.get("peak_bytes_in_use") for m in memory],
                  "bytes_in_use": [m.get("bytes_in_use") for m in memory]}
        emit(phase="placement", run="session-axis-4", **placed)
        if placed["device_set_sizes"] != [4] or placed["unsharded_placements"]:
            raise SmokeFailure(f"round tensors left the mesh: {placed}")
        if not all(m.get("peak_bytes_in_use") for m in memory):
            raise SmokeFailure(f"a device reports no memory in use: {placed}")

        sharded.arm_session_axis(1)  # disarm: default single-device placement
        del device_sets[:]
        single = served_run(counter, "one-device")
        if sorted(set(device_sets)) != [1]:
            raise SmokeFailure("disarmed run still placed over several "
                               f"devices: {sorted(set(device_sets))}")
        emit(phase="compare", four_chip_wave_seconds=armed["wave_seconds"],
             one_device_wave_seconds=single["wave_seconds"])
    finally:
        eb.to_dev = plain_to_dev
        sharded.arm_session_axis(1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run ONLY the session-axis phase: the waves over "
                         "four chips, then over one, in one process")
    args = ap.parse_args(argv)
    t0 = time.monotonic()
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        if not os.path.isdir(os.path.join(here, "mpcium_tpu")):
            raise SmokeFailure(f"the program is not beside this script "
                               f"({here} holds no mpcium_tpu/)")
        dev = accelerator(4 if args.four_chips else 1)
        emit(phase="device", **dev)
        from mpcium_tpu.utils import jax_cache

        emit(phase="compile_cache", dir=jax_cache.configure())
        counter = CompileCounter()
        if args.four_chips:
            four_chips(counter)
        else:
            served_run(counter, "one-chip")
    except SmokeFailure as e:
        emit(ok=False, error=str(e), total_seconds=time.monotonic() - t0)
        return 1
    emit(phase="total", total_seconds=time.monotonic() - t0,
         compiles=counter.snapshot())
    emit(ok=True, device=dev)
    return 0


if __name__ == "__main__":
    sys.exit(main())
