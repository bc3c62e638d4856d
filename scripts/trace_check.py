#!/usr/bin/env python
"""mpctrace CI gate (`make trace-check`, folded into `make check`).

Three checks, all zero-dependency:

1. The committed TRACE_sample.json validates against the Chrome
   trace-event schema (trace/schema.py) and still covers every layer the
   tracing work instruments: scheduler intake/queue/dispatch, per-round
   protocol spans, session spans, device phases.
2. Transcript equality: the SAME deterministic batched-signing run,
   traced and untraced, produces byte-identical round transcripts and
   signatures — tracing must be observationally free. Run twice: party
   to party through the in-process runner, and through real Sessions
   over a loopback fabric under the armed flight recorder (the served
   path's envelope and hello spans, the clock-anchor hook).
3. (unless --no-sweep) the mpclint + mpcflow + mpcshape static gate via
   scripts/check_all.py — span attributes that hit the secret taxonomy
   must go through the declassify registry, never into the baseline.

`--regen` rebuilds TRACE_sample.json from a live miniature cluster run
(batch signing through the scheduler under the flight recorder), then
validates it. Regeneration is the slow path; plain validation is fast.

Exit codes: 0 clean, 1 any check failed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
sys.path.insert(0, _ROOT)

SAMPLE = os.path.join(_ROOT, "TRACE_sample.json")

# the layers the sample must witness (acceptance list of the tracing PR)
REQUIRED_SPAN_LAYERS = {
    "scheduler intake": lambda n: n == "intake",
    "scheduler queue": lambda n: n == "queue",
    "scheduler dispatch": lambda n: n == "dispatch",
    "client SDK": lambda n: n.startswith("client:"),
    "manifest admission": lambda n: n == "host:manifest_admit",
    "batch preparation": lambda n: n == "host:batch_prepare",
    "hello barrier": lambda n: n == "wait:hello",
    "inbound envelopes": lambda n: n == "host:envelope_in",
    "result egress": lambda n: n == "host:result_egress",
    "protocol rounds": lambda n: n.startswith("round:"),
    "sessions": lambda n: n == "session",
    "device phases": lambda n: n.startswith("phase:"),
}


def _setup_cpu_jax() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    from mpcium_tpu.utils import jax_cache

    jax_cache.configure()


def regen_sample() -> dict:
    """Rebuild TRACE_sample.json: a miniature batch-signing soak (no
    chaos) through the full cluster under the armed flight recorder —
    the same capture path drills and soaks embed."""
    _setup_cpu_jax()
    from mpcium_tpu.soak import SoakConfig, run_soak
    from mpcium_tpu.utils import log

    log.init(level="ERROR")
    report = run_soak(SoakConfig(
        n_nodes=3, threshold=1, n_wallets=2,
        n_sign=4, burst_size=4, burst_gap_s=0.05, seed=42,
        interactive_fraction=0.5,
        chaos="",  # the sample documents the span model, not chaos
        batch_window_s=0.2, wait_timeout_s=420.0,
    ))
    doc = report["trace"]
    doc["otherData"]["sample"] = (
        "regenerate with: python scripts/trace_check.py --regen"
    )
    with open(SAMPLE, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    return doc


def check_sample() -> list:
    from mpcium_tpu.trace import validate_chrome

    errors = []
    try:
        with open(SAMPLE) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [f"TRACE_sample.json unreadable: {e!r} "
                f"(regenerate: python scripts/trace_check.py --regen)"]
    try:
        n = validate_chrome(doc)
    except Exception as e:  # noqa: BLE001 — collect, don't crash the gate
        return [f"TRACE_sample.json schema: {e}"]
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") != "M"}
    for layer, pred in REQUIRED_SPAN_LAYERS.items():
        if not any(pred(n) for n in names):
            errors.append(
                f"TRACE_sample.json: no span for layer {layer!r} "
                f"(have {sorted(names)[:12]}...)"
            )
    if not errors:
        print(f"trace-check: sample OK ({n} events, "
              f"{len(names)} span names)")
    return errors


def check_transcript_equality() -> list:
    """The same deterministic 2-party batched EdDSA signing run, traced
    and untraced: round transcripts and signatures must be identical —
    party to party through the runner, and through Sessions on a
    loopback fabric with the flight recorder armed."""
    _setup_cpu_jax()
    import random
    import tempfile
    import time

    from mpcium_tpu import trace
    from mpcium_tpu.engine import eddsa_batch as eb
    from mpcium_tpu.identity.identity import IdentityStore, generate_identity
    from mpcium_tpu.node.session import Session
    from mpcium_tpu.protocol.eddsa.batch_signing import (
        BatchedEDDSASigningParty,
    )
    from mpcium_tpu.protocol.runner import run_protocol
    from mpcium_tpu.transport.loopback import LoopbackFabric
    from mpcium_tpu.utils import tracing

    ids = ["n0", "n1"]
    # a known start: --regen's cluster leaves the recorder armed
    trace.disarm()
    trace.recorder.reset(ids)

    class DetRng:
        def __init__(self, seed):
            self._r = random.Random(seed)

        def token_bytes(self, n):
            return self._r.randbytes(n)

        def randbelow(self, n):
            return self._r.randrange(n)

    def make_parties(transcript):
        shares = eb.dealer_keygen_batch(2, ids, 1, rng=DetRng(5))
        parties = {
            pid: BatchedEDDSASigningParty(
                "trace-eq", pid, ids, shares[i],
                [b"a" * 32, b"b" * 32], rng=DetRng(11 + i),
            )
            for i, pid in enumerate(ids)
        }
        for p in parties.values():
            orig = p.receive

            def rec(m, _o=orig):
                transcript.append(
                    (m.round, m.from_id, m.to, repr(m.payload))
                )
                return _o(m)

            p.receive = rec
        return parties

    def signatures(parties):
        return {p: parties[p].result["signatures"].tobytes()
                for p in parties}

    def through_runner(traced):
        spans, transcript = [], []
        if traced:
            tracing.enable(sink=spans.append)
        try:
            parties = make_parties(transcript)
            run_protocol(parties)
        finally:
            tracing.disable()
        return transcript, signatures(parties), spans

    def through_sessions(traced):
        """Sessions deliver on the fabric's worker threads, so the
        transcript is compared as a sorted list."""
        transcript = []
        if traced:
            trace.arm(node_ids=ids)
        try:
            parties = make_parties(transcript)
            fabric = LoopbackFabric()
            with tempfile.TemporaryDirectory() as d:
                for n in ids:
                    generate_identity(n, d)
                sessions = [
                    Session(
                        session_id="trace-eq", party=parties[n], node_id=n,
                        participants=ids, transport=fabric.transport(),
                        identity=IdentityStore(d, n, {i: i for i in ids}),
                        broadcast_topic="trace-eq.bcast",
                        direct_topic_fn=lambda to: f"trace-eq.direct.{to}",
                    )
                    for n in ids
                ]
                try:
                    for s in sessions:
                        s.listen()
                    deadline = time.monotonic() + 600
                    for s in sessions:
                        s.wait(max(0.0, deadline - time.monotonic()))
                    tracing.clock_anchor()  # armed: the hook runs, idle
                finally:
                    for s in sessions:
                        s.close()
                    fabric.close()
            if not all(p.done for p in parties.values()):
                return [], {}, []
            spans = [s for _n, (ss, _d) in trace.recorder.snapshot_all(
                ids, clear=True).items() for s in ss]
        finally:
            trace.disarm()
        return sorted(transcript), signatures(parties), spans

    errors = []
    for how, one_run, must_span in (
        ("runner", through_runner, {"phase:bsign_nonce_commit"}),
        ("sessions", through_sessions,
         {"host:envelope_in", "wait:hello", "session",
          "phase:bsign_nonce_commit"}),
    ):
        t_off, sig_off, s_off = one_run(False)
        t_on, sig_on, s_on = one_run(True)
        tag = f"transcript-equality ({how})"
        if not t_off or not sig_off:
            errors.append(f"{tag}: the untraced run did not finish")
        if s_off:
            errors.append(f"{tag}: spans emitted while disabled")
        missing = must_span - {s["name"] for s in s_on}
        if missing:
            errors.append(f"{tag}: traced run lacks spans {sorted(missing)}")
        if t_off != t_on:
            errors.append(f"{tag}: traced run CHANGED the round transcript")
        if sig_off != sig_on:
            errors.append(f"{tag}: traced run CHANGED the signatures")
        if not errors:
            print(f"trace-check: transcript equality OK through the {how} "
                  f"({len(t_off)} messages, {len(s_on)} spans)")
    return errors


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--regen", action="store_true",
                    help="rebuild TRACE_sample.json from a live run first")
    ap.add_argument("--no-sweep", action="store_true",
                    help="skip the mpclint/mpcflow/mpcshape sweep (already run by "
                         "the caller, e.g. make check)")
    args = ap.parse_args(argv)

    errors = []
    if args.regen:
        regen_sample()
    errors += check_sample()
    errors += check_transcript_equality()

    if not args.no_sweep:
        import check_all

        rc = check_all.main([])
        if rc != 0:
            errors.append(f"static sweep failed (check_all rc={rc})")

    for e in errors:
        print(f"TRACE-CHECK FAIL: {e}", file=sys.stderr)
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
