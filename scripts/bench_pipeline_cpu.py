#!/usr/bin/env python
"""CPU A/B proof for the zero-idle cohort pipeline (ISSUE 17).

Runs the real batched-Ed25519 engine at one batch size under K=1 (the
serial transcript oracle) and K=2 (counter-phase cohorts), with mpctrace
armed, and writes ``BENCH_pipeline_cpu.json``:

- signatures must be BYTE-identical across K (the transcript contract);
- the span-derived ``tracing.device_idle_fraction`` must be STRICTLY
  lower at K=2 — the host egress stages drain behind the other cohort's
  device rounds instead of extending the serial tail.

This is the degraded-host half of the round-10 ledger (the decision
numbers are TPU, measurement-owed on ROADMAP item 4); it exists so the
scheduling win is demonstrated, not asserted, on every host that can
run the tier-1 suite. Ed25519 is the vehicle because its kernels
compile in seconds on a 1-core CPU host where GG18's secp ladders need
minutes (test_gg18_batch.py policy); the K-sweep bit-identity of GG18
itself is tests/test_pipeline.py (slow tier).

With ``--device`` (the campaign's live-window step) the CPU pin is
skipped so the same A/B runs on whatever chip JAX finds, and ``--k``
widens the sweep (the owed matrix is K∈{1,2,4} at equal B).

Usage: JAX_PLATFORMS=cpu python scripts/bench_pipeline_cpu.py [--b 8]
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

if "--device" not in sys.argv:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
sys.path.insert(0, _ROOT)

OUT_BASENAME = "BENCH_pipeline_cpu.json"


class DetRng:
    """Hash-counter CSPRNG stand-in (tests/test_pipeline.py fixture):
    identical seeds draw identical streams, so the K=1 and K=2 runs
    consume byte-identical nonce/blind material."""

    def __init__(self, seed: int):
        self.seed = seed
        self.ctr = 0

    def token_bytes(self, n: int) -> bytes:
        out = bytearray()
        while len(out) < n:
            out += hashlib.sha256(
                b"pipebench|%d|%d" % (self.seed, self.ctr)
            ).digest()
            self.ctr += 1
        return bytes(out[:n])

    def randbelow(self, n: int) -> int:
        return int.from_bytes(self.token_bytes(40), "big") % n


def _one_run(ids, shares, messages, k: int):
    from mpcium_tpu.engine import eddsa_batch as eb
    from mpcium_tpu.utils import tracing

    signer = eb.BatchedCoSigners(ids[:2], shares[:2], rng=DetRng(42))
    spans: list = []
    tracing.enable(sink=spans.append)
    try:
        t0 = time.perf_counter()
        sigs, ok = signer.sign(messages, cohorts=k)
        wall_s = time.perf_counter() - t0
    finally:
        tracing.disable()
    import numpy as np

    assert np.asarray(ok).all(), f"K={k} produced invalid signatures"
    return {
        "sig_sha256": hashlib.sha256(
            np.asarray(sigs).tobytes()
        ).hexdigest(),
        "wall_s": round(wall_s, 4),
        "device_idle_fraction": round(
            tracing.device_idle_fraction(spans), 6
        ),
        "phase_s": {
            k2: round(v, 5) for k2, v in tracing.phase_share(spans).items()
        },
        "n_spans": len(spans),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--b", type=int, default=8, help="batch size (pow-2)")
    p.add_argument("--k", default="1,2",
                   help="comma list of cohort counts to A/B (K=1 first)")
    p.add_argument("--device", action="store_true",
                   help="skip the CPU pin — run on whatever JAX finds")
    p.add_argument("--lenient", action="store_true",
                   help="report but do not fail the idle comparison "
                        "(rehearsal: sub-ms CPU idle fractions are noise; "
                        "bit-identity stays a hard failure)")
    p.add_argument("--out", default=os.path.join(_ROOT, OUT_BASENAME))
    args = p.parse_args(argv)
    ks = sorted({int(x) for x in args.k.split(",") if x.strip()})
    if 1 not in ks:
        ks.insert(0, 1)  # K=1 is the serial oracle every K compares to

    from mpcium_tpu.utils import jax_cache

    jax_cache.configure()

    from mpcium_tpu.engine import eddsa_batch as eb
    from mpcium_tpu.perf.envfp import env_fingerprint

    B = args.b
    ids = ["n0", "n1", "n2"]
    shares = eb.dealer_keygen_batch(B, ids, 1, rng=DetRng(3))
    messages = [DetRng(9).token_bytes(32) for _ in range(B)]

    # warm every (K, width) compile signature OUTSIDE the measured runs
    for k in ks:
        signer = eb.BatchedCoSigners(ids[:2], shares[:2], rng=DetRng(42))
        _sigs, ok = signer.sign(messages, cohorts=k)
        assert ok.all()

    runs = {str(k): _one_run(ids, shares, messages, k) for k in ks}

    identical = all(
        runs[str(k)]["sig_sha256"] == runs["1"]["sig_sha256"] for k in ks
    )
    idle_1 = runs["1"]["device_idle_fraction"]
    idle_2 = runs["2"]["device_idle_fraction"] if 2 in ks else None
    doc = {
        "comment": (
            "CPU A/B proof of the counter-phase cohort pipeline "
            "(ISSUE 17, ROADMAP item 4): real batched-Ed25519 engine, "
            "K=1 serial oracle vs K=2 cohorts, mpctrace-armed. "
            "Signatures byte-identical; span-derived device idle "
            "fraction strictly lower at K=2. Degraded-host evidence "
            "only — TPU numbers are measurement-owed. Regenerate with "
            "scripts/bench_pipeline_cpu.py."
        ),
        "engine": "eddsa.sign",
        "batch": B,
        "cohorts": ks,
        "runs": runs,
        "signatures_bit_identical": identical,
        "idle_fraction_k1": idle_1,
        "idle_fraction_k2": idle_2,
        "idle_collapse_ratio": (
            round(idle_2 / idle_1, 4)
            if idle_1 and idle_2 is not None else None
        ),
        "env": env_fingerprint(),
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S", time.gmtime()),
    }
    for k in ks:
        doc[f"idle_fraction_k{k}"] = runs[str(k)]["device_idle_fraction"]
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(json.dumps({k: v for k, v in doc.items() if k != "comment"}))
    if not identical:
        print("FAIL: signatures differ across K", file=sys.stderr)
        return 1
    if idle_2 is not None and not idle_2 < idle_1:
        verdict = (
            f"K=2 idle {idle_2} not below K=1 idle {idle_1}"
        )
        if not args.lenient:
            print(f"FAIL: {verdict}", file=sys.stderr)
            return 1
        print(f"warn (lenient): {verdict} — idle claim stays owed")
    print(f"ok: idle {idle_1} (K=1) -> {idle_2} (K=2), sigs identical")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
