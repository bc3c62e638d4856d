#!/usr/bin/env python
"""Compile the served GG18 round programs, and the programs that build a
party's comb tables, for a DESCRIBED TPU v5e, no chip attached, and print
what each costs: lines of StableHLO, seconds to lower and to compile, the
process's peak memory.

    python scripts/gg18_compile_check.py [--wave 16] [program ...]

How: one batch of three signers is run with every ``gg18_*`` program and
every comb-build program (``ops/modmul.py``: ``_k_comb_rows``, one shape a
modulus width and lane count, and ``_k_comb_take``) replaced by
``jax.eval_shape`` of itself (real 2048-bit moduli, nothing computed),
which records each program's argument shapes; then each program is lowered
and compiled for ``v5e:2x2`` device 0 with the fused Pallas mulmod (the
chip's default). Nothing runs, so this says nothing about results or
speed: it is what ``compile-wall`` (ROADMAP.md) is watched with, and it
costs no chip time. A compile's time here was within a fifth of the chip
host's (PR 29).
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


# a context's comb tables are built by these (mpcium_tpu/ops/modmul.py); they
# run where contexts are built, not in a wave, and are no round programs
BUILD_PROGRAMS = ("_k_comb_rows", "_k_comb_take")


def _spec(args, sharding=None):
    """Arguments as ShapeDtypeStructs; a static one (an int) as it is."""
    import jax

    return jax.tree.map(
        lambda x: x if isinstance(x, int)
        else jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), args)


def _build_label(name: str, args) -> str:
    """A build program is asked for several shapes: its name, its first
    argument's shape (of a tuple: its length and its first's), its statics."""
    first = args[0]
    shape = ((len(first),) + first[0].shape if isinstance(first, tuple)
             else first.shape)
    statics = "".join(f",{x}" for x in args if isinstance(x, int))
    return f"{name}[{'x'.join(map(str, shape))}{statics}]"


def record_shapes(wave: int) -> dict:
    """{program's label: (the jitted program, its arguments as
    ShapeDtypeStructs)}: a round program once, a build program once a
    shape it was asked for (labelled with that shape)."""
    import jax
    import numpy as np

    from mpcium_tpu.cluster import load_test_preparams
    from mpcium_tpu.engine import gg18_batch as gb
    from mpcium_tpu.ops import modmul as mm
    from mpcium_tpu.protocol.ecdsa import batch_signing as bs
    from mpcium_tpu.protocol.runner import run_protocol

    shapes, outs, real = {}, {}, {}

    def stub_of(module, name, label_of):
        fn = real[module, name] = getattr(module, name)

        def stub(*args):
            label = label_of(args)
            if label not in shapes:
                shapes[label] = (fn, _spec(args))
                outs[label] = jax.tree.map(
                    lambda s: np.zeros(s.shape, s.dtype),
                    fn.eval_shape(*shapes[label][1]))
            return outs[label]

        setattr(module, name, stub)

    for names in gb.ROUND_PROGRAMS.values():
        for name in names:
            stub_of(gb, name, lambda _a, _n=name: _n)
    for name in BUILD_PROGRAMS:
        stub_of(mm, name, lambda a, _n=name: _build_label(_n, a))
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
        for (module, name), fn in real.items():
            setattr(module, name, fn)
    return shapes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--wave", type=int, default=16)
    ap.add_argument("programs", nargs="*")
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

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
    # a build program's name selects every shape it was asked for
    for name in [n for n in sorted(shapes) if not args.programs
                 or n.split("[")[0] in args.programs or n in args.programs]:
        program, spec = shapes[name]
        t0 = time.monotonic()
        lowered = program.lower(*_spec(spec, chip))
        t1 = time.monotonic()
        lines = lowered.as_text().count("\n")
        lowered.compile()
        t2 = time.monotonic()
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
        total = [total[0] + lines, total[1] + t1 - t0, total[2] + t2 - t1]
        print(f"{name:34s} lines={lines:7d} lower={t1 - t0:6.1f}s "
              f"compile={t2 - t1:6.1f}s peak_rss={rss:5.2f}GB", flush=True)
    print(f"{'all':34s} lines={total[0]:7d} lower={total[1]:6.1f}s "
          f"compile={total[2]:6.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
