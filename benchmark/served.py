"""The system under test, as the benchmark holds it: one in-process cluster
built from a configuration file and the seed, a client that submits sign
requests and records when each was sent and when its result event came,
and the reads of the program's own counters, spans and compile ledger.

Adapted from ``chip_smoke.py`` (``Served``, ``CompileCounter``): the
cluster set-up, the main-thread warm compile and the books are the same;
what is new is that every size comes from the configuration, every request
is timed on its own, and nothing here decides what traffic to send (the
traffic generators under ``traffic/`` do).
"""
from __future__ import annotations

import random
import shutil
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, Optional


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
        if event == self._HIT:
            with self._lock:
                self.hits += 1
        elif event == self._MISS:
            with self._lock:
                self.misses += 1

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {"requests": self.requests, "cache_hits": self.hits,
                    "cache_misses": self.misses}


def _preparams(n_nodes: int, fixtures: dict) -> dict:
    """Paillier pre-parameters for every node from the program's committed
    fixtures (three of them, reused in turn): Ed25519 signing never reads
    them, and a node handed none searches for safe primes at start-up,
    which takes a random minute."""
    have = [fixtures[k] for k in sorted(fixtures)]
    return {f"node{i}": have[i % len(have)] for i in range(n_nodes)}


@dataclass
class Request:
    """One sign request as the client saw it (host monotonic ns)."""

    tx_id: str
    wave: int
    wallet: int  # index into the population
    digest: bytes
    submit_ns: int
    done_ns: Optional[int] = None
    success: bool = False
    signature: bytes = b""
    error: str = ""


@dataclass
class Wave:
    index: int
    measured: bool
    size: int
    t0_ns: int
    submitted_ns: int
    done_ns: int
    requests: List[Request] = field(default_factory=list)
    compile_requests: int = 0
    batches_fired: int = 0

    @property
    def seconds(self) -> float:
        return (self.done_ns - self.t0_ns) / 1e9

    @property
    def submit_seconds(self) -> float:
        return (self.submitted_ns - self.t0_ns) / 1e9


class Served:
    """The cluster of one configuration, its wallet population from the
    seed, and the client-side ledger of one run."""

    def __init__(self, config: dict, seed: int, counter: CompileCounter):
        from mpcium_tpu.cluster import LocalCluster, load_test_preparams
        from mpcium_tpu.engine.pipeline import resolve_cohorts
        from mpcium_tpu.protocol.base import KeygenShare, party_xs

        from . import wallets

        scheme, serving = config["scheme"], config["serving"]
        self.config = config
        self.counter = counter
        self.n_nodes = scheme["n_nodes"]
        self.threshold = scheme["threshold"]
        self.quorum = scheme["served_quorum"]
        self.digest_bytes = scheme["digest_bytes"]
        self.wave_size = serving["batch_max_batch"]
        self.n_wallets = config["population"]["wallets"]
        self.deadline_s = serving["deadline_s"]
        self.cohorts = resolve_cohorts(self.wave_size)
        self.shape = f"B{self.wave_size}|q{self.quorum}"
        self._root = tempfile.mkdtemp(prefix="mpcium-bench-")  # under TMPDIR
        self.cluster = LocalCluster(
            n_nodes=self.n_nodes,
            threshold=self.threshold,
            root_dir=self._root,
            preparams=_preparams(self.n_nodes, load_test_preparams()),
            batch_signing=serving["batch_signing"],
            batch_window_s=serving["batch_window_s"],
            reply_timeout_s=self.deadline_s,
            batch_max_batch=self.wave_size,
            batch_manifest_timeout_s=serving["batch_manifest_timeout_s"],
            loopback_workers=(self.wave_size
                              + serving["loopback_workers_over_wave"]),
        )
        ids = self.cluster.node_ids
        rng = random.Random(seed)
        xs = party_xs(ids)
        self.pubkeys, shares = wallets.make_wallets(
            self.n_wallets, xs, self.threshold, rng)
        self.wallet_ids = [f"w{seed}-{w}" for w in range(self.n_wallets)]
        participants = sorted(ids)
        records = {
            nid: [KeygenShare(
                key_type=scheme["key_type"], share=shares[nid][w],
                self_x=xs[nid], public_key=self.pubkeys[w],
                participants=participants, threshold=self.threshold)
                for w in range(self.n_wallets)]
            for nid in ids
        }

        def store(nid: str) -> None:
            node = self.cluster.nodes[nid]
            for record, wid in zip(records[nid], self.wallet_ids):
                node.save_share(record, wid)

        # a thread per node: each store seals and writes under its own
        # lock, and OpenSSL and the file system release the interpreter
        with ThreadPoolExecutor(max_workers=len(ids)) as pool:
            for done in [pool.submit(store, nid) for nid in ids]:
                done.result()
        self._warm_shares = {nid: records[nid][: self.wave_size]
                             for nid in ids[: self.quorum]}
        self._lock = threading.Lock()
        self._open: Dict[str, Request] = {}
        self._left = 0
        self._wave_done = threading.Event()
        self._sub = self.cluster.client.on_sign_result(self._on_result)
        self.strays = 0  # result events for no open request

    def close(self) -> None:
        try:
            self._sub.unsubscribe()
            self.cluster.close()
        finally:
            shutil.rmtree(self._root, ignore_errors=True)

    # -- the warm compile ---------------------------------------------------

    def warm(self) -> None:
        """One party-level batch at the cell's one shape, in THIS (the
        main) thread, through the synchronous in-process runner: every
        kernel the node threads will call is compiled (or loaded from the
        cache) before three of them can meet it cold at once. Uses the
        first wave-size wallets' shares and fixed digests."""
        from mpcium_tpu.protocol.eddsa.batch_signing import (
            BatchedEDDSASigningParty,
        )
        from mpcium_tpu.protocol.runner import run_protocol

        ids = list(self._warm_shares)
        digests = [bytes([i % 256]) * self.digest_bytes
                   for i in range(self.wave_size)]
        parties = {
            pid: BatchedEDDSASigningParty(
                "bench-warm", pid, ids, self._warm_shares[pid], digests,
                cohorts=self.cohorts)
            for pid in ids
        }
        run_protocol(parties)
        for pid, p in parties.items():
            if not bool(p.result["ok"].all()):
                raise RuntimeError(f"warm batch failed verification at {pid}")
        self._warm_shares = {}

    # -- the client ---------------------------------------------------------

    def _on_result(self, ev) -> None:
        from mpcium_tpu import wire

        now = time.monotonic_ns()
        with self._lock:
            req = self._open.pop(ev.tx_id, None)
            if req is None:
                self.strays += 1
                return
            req.done_ns = now
            req.success = ev.result_type == wire.RESULT_SUCCESS
            if req.success:
                try:
                    req.signature = bytes.fromhex(ev.signature)
                except ValueError:
                    req.signature = b""
            else:
                req.error = ev.error_reason
            self._left -= 1
            if self._left <= 0:
                self._wave_done.set()

    def run_wave(self, index: int, measured: bool, wallets: List[int],
                 digests: List[bytes], params: dict,
                 timeout_s: float) -> Wave:
        """Submit one request per (wallet, digest) back to back, then wait
        for every result event (or the timeout). Returns the wave with
        each request's own clock readings."""
        from mpcium_tpu import wire

        client = self.cluster.client
        reqs = [Request(tx_id=f"bench-{index}-{i}", wave=index, wallet=w,
                        digest=d, submit_ns=0)
                for i, (w, d) in enumerate(zip(wallets, digests))]
        with self._lock:
            self._open = {r.tx_id: r for r in reqs}
            self._left = len(reqs)
            self._wave_done.clear()
        compiles0 = self.counter.snapshot()["requests"]
        fired0 = self.counter_total("scheduler.batches_fired_total")
        deadline_ms = int(self.deadline_s * 1000)
        priority = (wire.PRIORITY_BULK if params["priority"] == "bulk"
                    else wire.PRIORITY_INTERACTIVE)
        t0 = time.monotonic_ns()
        for r in reqs:
            r.submit_ns = time.monotonic_ns()
            client.sign_transaction(wire.SignTxMessage(
                key_type=self.config["scheme"]["key_type"],
                wallet_id=self.wallet_ids[r.wallet],
                network_internal_code=params["network_internal_code"],
                tx_id=r.tx_id,
                tx=r.digest,
                deadline_ms=deadline_ms,
                priority=priority,
            ))
        submitted = time.monotonic_ns()
        self._wave_done.wait(timeout_s)
        done = time.monotonic_ns()
        with self._lock:
            self._open = {}
        wave = Wave(
            index=index, measured=measured, size=len(reqs), t0_ns=t0,
            submitted_ns=submitted, done_ns=done, requests=reqs,
            compile_requests=self.counter.snapshot()["requests"] - compiles0,
            batches_fired=int(
                self.counter_total("scheduler.batches_fired_total") - fired0),
        )
        return wave

    # -- the program's own counters, spans and ledgers ------------------------

    def metrics_snapshot(self) -> Dict[str, dict]:
        return self.cluster.metrics_snapshot()

    def counter_total(self, name: str) -> float:
        return sum(s["counters"].get(name, 0.0)
                   for s in self.metrics_snapshot().values())

    def drain_spans(self) -> List[dict]:
        """The flight recorder's finished spans since the last drain, raw
        (``t0_ns``/``t1_ns`` on ``time.monotonic_ns``), every node's."""
        from mpcium_tpu.trace import recorder

        out: List[dict] = []
        for _nid, (spans, _dropped) in recorder.snapshot_all(
                clear=True).items():
            out.extend(spans)
        return out

    def party_shapes(self) -> List[str]:
        from mpcium_tpu.perf import compile_watch

        return sorted({e["shape"] for e in compile_watch.entries()
                       if e["engine"] == "party.eddsa"})
