#!/usr/bin/env python
"""Compile the served GG18 round programs for a DESCRIBED TPU v5e, no chip
attached, and print what each costs: lines of StableHLO, seconds to lower
and to compile, the process's peak memory.

    python scripts/gg18_compile_check.py [--wave 16] [program ...]

How: one batch of three signers is run with every ``gg18_*`` program
replaced by ``jax.eval_shape`` of itself (real 2048-bit modulus contexts,
nothing computed), which records each program's argument shapes; then each
program is lowered and compiled for ``v5e:2x2`` device 0 with the fused
Pallas mulmod (the chip's default). Nothing runs, so this says nothing
about results or speed: it is what ``compile-wall`` (ROADMAP.md) is
watched with, and it costs no chip time. A compile's time here was within
a fifth of the chip host's (PR 29).
"""
from __future__ import annotations

import argparse
import os
import resource
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

IDS = ["node0", "node1", "node2"]


def record_shapes(wave: int) -> dict:
    """{program name: its arguments as ShapeDtypeStructs}."""
    import jax
    import numpy as np

    from mpcium_tpu.cluster import load_test_preparams
    from mpcium_tpu.engine import gg18_batch as gb
    from mpcium_tpu.protocol.ecdsa import batch_signing as bs
    from mpcium_tpu.protocol.runner import run_protocol

    shapes, outs, real = {}, {}, {}
    for names in gb.ROUND_PROGRAMS.values():
        for name in names:
            real[name] = getattr(gb, name)

            def stub(*args, _n=name):
                if _n not in shapes:
                    shapes[_n] = jax.tree.map(
                        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype),
                        args)
                    outs[_n] = jax.eval_shape(real[_n], *args)
                return jax.tree.map(lambda s: np.zeros(s.shape, s.dtype),
                                    outs[_n])

            setattr(gb, name, stub)
    holds, gb.agg_holds = gb.agg_holds, lambda *a: True
    try:
        shares = gb.dealer_keygen_secp_batch(
            wave, IDS, threshold=1, preparams=load_test_preparams(bits=2048))
        digests = [bytes([i % 256]) * 32 for i in range(wave)]
        run_protocol({
            pid: bs.BatchedECDSASigningParty("bsign:shapes", pid, IDS,
                                             shares[i], digests)
            for i, pid in enumerate(IDS)})
    finally:
        gb.agg_holds = holds
        for name, fn in real.items():
            setattr(gb, name, fn)
    return shapes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--wave", type=int, default=16)
    ap.add_argument("programs", nargs="*")
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from mpcium_tpu.engine import gg18_batch as gb
    from mpcium_tpu.ops import modmul as mm
    from mpcium_tpu.ops import pallas_mulmod

    jax.config.update("jax_enable_compilation_cache", False)
    # the chip's path: the fused kernel, compiled and not interpreted
    # (before the programs are first traced: a trace is kept)
    mm.MULMOD_IMPL = "pallas"
    fused = pallas_mulmod.mulmod
    pallas_mulmod.mulmod = (
        lambda *a, interpret=False, **k: fused(*a, interpret=False, **k))
    shapes = record_shapes(args.wave)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    total = [0, 0.0, 0.0]
    for name in args.programs or sorted(shapes):
        on_chip = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            shapes[name])
        t0 = time.monotonic()
        lowered = getattr(gb, name).lower(*on_chip)
        t1 = time.monotonic()
        lines = lowered.as_text().count("\n")
        lowered.compile()
        t2 = time.monotonic()
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
        total = [total[0] + lines, total[1] + t1 - t0, total[2] + t2 - t1]
        print(f"{name:18s} lines={lines:7d} lower={t1 - t0:6.1f}s "
              f"compile={t2 - t1:6.1f}s peak_rss={rss:5.2f}GB", flush=True)
    print(f"{'all':18s} lines={total[0]:7d} lower={total[1]:6.1f}s "
          f"compile={total[2]:6.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
