"""Microbench: the host-side IKNP extension stage, thread-count A/B.

Measures exactly the work the OT-MtA pipeline hides behind device
compute — per-chunk PRG expansion of the three seed matrices
(t0/t1/tD), the U/Q xor assembly, the packed bit-matrix transpose and
the per-OT pad hashing for two payload sets — at M = 2^20 OTs
(B = 4096 signing lanes), pure host code, no JAX involved. Runs the
identical byte stream at MPCIUM_NATIVE_THREADS=1 and =N (default 4; the
thread knob is read per native call, so one process measures both) and
prints a JSON line with the speedup. Outputs are asserted bit-identical
across thread counts.

This is the CPU-measurable side of the ISSUE-2 acceptance gate: on a
multi-core host the threaded native path must cut the stage's
wall-clock >= 2x at 4 threads. On a single-core container (the
dev-loop host: nproc == 1) the ratio is honestly ~1.0x — the JSON
carries "cores" so the driver can tell the two apart.

--device adds the host-vs-device hash-suite A/B (ISSUE 11): each
extension sub-stage — PRG expansion, packed bit-transpose, pad
hashing — timed on the host/native path and on the ops.hash_suite
device kernels (warm, post-compile), outputs asserted bit-identical,
and the comparison emitted in the same JSON record under
ot_host_*/ot_device_* keys. JAX is only imported in this mode; the default
host-only run stays JAX-free.

Usage: python scripts/bench_ot_host.py [--m 1048576] [--threads 4]
                                       [--device]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mpcium_tpu import native  # noqa: E402
from mpcium_tpu.protocol.ecdsa import mta_ot  # noqa: E402

KAPPA = mta_ot.KAPPA


def _stage(seeds3, delta, delta_packed, delta_rows, r_packed, M, tag):
    """One full host extension stage: PRG x3, U/Q assembly, transpose +
    pads for two payload sets, both roles. Returns a digest of every
    output so the A/B runs can be asserted identical."""
    k0, k1, kD = seeds3
    n_bytes = M // 8
    t0 = mta_ot._prg(k0, n_bytes, tag)
    t1 = mta_ot._prg(k1, n_bytes, tag)
    U = native.xor_rows(t1, t0)            # t1 buffer becomes U
    native.xor_rows(U, r_packed)
    tD = mta_ot._prg(kD, n_bytes, tag)
    for r in delta_rows:
        tD[r] ^= U[r]                      # Q matrix, in place
    prefixes = [b"bench-pad|" + tag + b"|s%d" % s for s in range(2)]
    padsA = mta_ot._derive_pads_multi(prefixes, t0, M)
    padsB = mta_ot._derive_pads_multi(
        prefixes, tD, M, delta=delta_packed
    )
    acc = np.zeros(32, np.uint64)
    for p in padsA:
        acc += p[:64].astype(np.uint64).sum(axis=0)
    for p0, p1 in padsB:
        acc += p0[:64].astype(np.uint64).sum(axis=0)
        acc += p1[:64].astype(np.uint64).sum(axis=0)
    return U[:, :8].copy(), acc


def _timed(n_runs, *args):
    best = float("inf")
    digest = None
    for _ in range(n_runs):
        t0 = time.perf_counter()
        digest = _stage(*args)
        best = min(best, time.perf_counter() - t0)
    return best, digest


def _best_of(n_runs, fn):
    best = float("inf")
    out = None
    for _ in range(n_runs):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def _device_ab(seeds3, M, tag, n_runs):
    """Per-sub-stage host vs device A/B: PRG, transpose, pads. Each
    device kernel is compiled once (warmup) and timed warm with
    block_until_ready; outputs are asserted bit-identical to the host
    path before any timing is reported."""
    import jax
    import jax.numpy as jnp

    from mpcium_tpu.ops import hash_suite as hs

    k0 = seeds3[0]
    n_bytes = M // 8
    nblk = -(-n_bytes // 32)
    prg_prefix = b"mpcium-ot-prg|" + tag
    pad_prefix = b"bench-pad|" + tag + b"|s0"

    # --- PRG expansion: (KAPPA, 32) seeds -> (KAPPA, M/8) keystream
    host_prg_s, t0_host = _best_of(
        n_runs, lambda: mta_ot._prg(k0, n_bytes, tag)
    )
    dev_prg = hs.prg_expand_device(prg_prefix, k0, nblk)  # compile
    dev_prg.block_until_ready()
    device_prg_s, dev_prg = _best_of(
        n_runs,
        lambda: hs.prg_expand_device(prg_prefix, k0, nblk)
        .block_until_ready(),
    )
    assert np.array_equal(
        np.asarray(dev_prg)[:, :n_bytes], t0_host
    ), "device PRG diverged from host PRG"

    # --- packed bit-transpose: (KAPPA, M/8) -> (M, KAPPA/8)
    def host_transpose():
        rows = native.ot_transpose(t0_host) if native.available() else None
        if rows is None:
            rows = mta_ot._pack(mta_ot._unpack(t0_host, M).T)
        return rows

    host_transpose_s, rows_host = _best_of(n_runs, host_transpose)
    t0_dev = jnp.asarray(t0_host)
    hs.ot_transpose_device(t0_dev).block_until_ready()  # compile
    device_transpose_s, rows_dev = _best_of(
        n_runs,
        lambda: hs.ot_transpose_device(t0_dev).block_until_ready(),
    )
    assert np.array_equal(
        np.asarray(rows_dev), rows_host
    ), "device transpose diverged from host transpose"

    # --- pad hashing: H(prefix || row || le32(j)) per OT -> (M, 32)
    idx = np.arange(M, dtype=np.uint32).view(np.uint8).reshape(M, 4)

    def host_pads():
        return mta_ot._hash_rows(
            pad_prefix, np.concatenate([rows_host, idx], axis=1)
        )

    host_pads_s, pads_host = _best_of(n_runs, host_pads)
    pref_dev = jnp.asarray(np.frombuffer(pad_prefix, np.uint8))
    rows_dev = jnp.asarray(rows_host)
    m_off = jnp.uint32(0)
    hs.pad_hash_device(pref_dev, rows_dev, m_off).block_until_ready()
    device_pads_s, pads_dev = _best_of(
        n_runs,
        lambda: hs.pad_hash_device(pref_dev, rows_dev, m_off)
        .block_until_ready(),
    )
    assert np.array_equal(
        np.asarray(pads_dev), pads_host
    ), "device pads diverged from host pads"

    host_total = host_prg_s + host_transpose_s + host_pads_s
    dev_total = device_prg_s + device_transpose_s + device_pads_s
    return {
        "device_platform": jax.devices()[0].platform,
        "ot_host_prg_s": round(host_prg_s, 4),
        "ot_device_prg_s": round(device_prg_s, 4),
        "ot_host_transpose_s": round(host_transpose_s, 4),
        "ot_device_transpose_s": round(device_transpose_s, 4),
        "ot_host_pads_s": round(host_pads_s, 4),
        "ot_device_pads_s": round(device_pads_s, 4),
        "ot_host_stage_s": round(host_total, 4),
        "ot_device_stage_s": round(dev_total, 4),
        "ot_device_stage_speedup": (
            round(host_total / dev_total, 3) if dev_total > 0 else 0.0
        ),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=1 << 20, help="OT count M")
    ap.add_argument("--threads", type=int, default=4)
    ap.add_argument("--runs", type=int, default=2)
    ap.add_argument(
        "--device", action="store_true",
        help="also A/B each sub-stage against the ops.hash_suite device "
             "kernels (imports JAX)",
    )
    args = ap.parse_args()

    rng = np.random.default_rng(42)
    seeds3 = tuple(
        rng.integers(0, 256, size=(KAPPA, 32), dtype=np.uint8)
        for _ in range(3)
    )
    delta = rng.integers(0, 2, size=KAPPA, dtype=np.uint8)
    delta_packed = np.packbits(delta, bitorder="little")
    delta_rows = np.nonzero(delta)[0]
    r_packed = rng.integers(0, 256, size=args.m // 8, dtype=np.uint8)
    stage_args = (
        seeds3, delta, delta_packed, delta_rows, r_packed, args.m, b"ab",
    )

    os.environ["MPCIUM_NATIVE_THREADS"] = "1"
    t_1, d_1 = _timed(args.runs, *stage_args)
    os.environ["MPCIUM_NATIVE_THREADS"] = str(args.threads)
    t_n, d_n = _timed(args.runs, *stage_args)
    os.environ.pop("MPCIUM_NATIVE_THREADS", None)

    assert np.array_equal(d_1[0], d_n[0]) and np.array_equal(
        d_1[1], d_n[1]
    ), "thread count changed the transcript"

    record = {
        "metric": "ot_host_extension_stage_speedup",
        "value": round(t_1 / t_n, 3) if t_n > 0 else 0.0,
        "unit": "x (1 thread / %d threads wall)" % args.threads,
        "m_ots": args.m,
        "threads": args.threads,
        "cores": os.cpu_count(),
        "native": native.available(),
        "stage_s_1thread": round(t_1, 3),
        "stage_s_nthread": round(t_n, 3),
    }
    if args.device:
        record.update(_device_ab(seeds3, args.m, b"ab", args.runs))
    print(json.dumps(record))


if __name__ == "__main__":
    main()
