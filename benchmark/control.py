#!/usr/bin/env python
"""The control, on the chip, at a cell's own size: show that the comparison
which decides ``correct`` fails when it should.

    python benchmark/control.py --workload <cell> --seeds 11,12,13 --seconds 15

For each seed, in ONE process (later seeds reuse the compiled programs):
build the cluster from the seed, run a short window at the cell's own
load, and decide ``correct`` for the sound run (must be true). Then break
the guarantee the configuration states, on the benchmark's side — the
scheme is integer arithmetic, so there is no lower precision to compute
in — and decide again (must be false each time):

  flip_bit   one seeded bit of one returned signature is altered;
  other_key  one signature is checked under the next wallet's public key.

The benchmark's own runs never run this. Exit 0 only if every sound run is
correct and every control is not. The last line is a JSON summary.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def flip_bit(served, run, rng: random.Random):
    r = rng.choice(run.measured)
    bit = rng.randrange(len(r.signature) * 8)
    sound = r.signature
    altered = bytearray(sound)
    altered[bit // 8] ^= 1 << (bit % 8)
    r.signature = bytes(altered)
    return lambda: setattr(r, "signature", sound)


def other_key(served, run, rng: random.Random):
    w = rng.choice(run.measured).wallet
    v = (w + 1) % len(served.pubkeys)
    keys = served.pubkeys
    keys[w], keys[v] = keys[v], keys[w]

    def undo():
        keys[w], keys[v] = keys[v], keys[w]
    return undo


CONTROLS = {"flip_bit": flip_bit, "other_key": other_key}


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import harness

    cell = harness.Cell(root, args.workload)
    try:
        _dev, counter = harness.prepare(cell)
    except harness.NoAccelerator as e:
        print(str(e), file=sys.stderr)
        return 3
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        served, run = harness.measure(cell, seed, args.seconds, False,
                                      counter, time.monotonic_ns())
        try:
            row = {"seed": seed, "compared": len(run.measured),
                   "sound_correct": harness.check(
                       served, run, cell.traffic)["correct"]}
            rng = random.Random(seed)
            for name, control in CONTROLS.items():
                undo = control(served, run, rng)
                row[name + "_correct"] = harness.check(
                    served, run, cell.traffic)["correct"]
                undo()
            rows.append(row)
            harness.emit(phase="control", **row)
        finally:
            served.close()
    ok = all(r["sound_correct"] and not any(
        r[c + "_correct"] for c in CONTROLS) for r in rows)
    print(json.dumps({"control_holds": ok, "runs": rows}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
