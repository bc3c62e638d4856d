#!/usr/bin/env python3
"""Pre-warm the compile cache from the committed compile surface.

Walks the warm manifest (COMPILE_SURFACE.json knobs × engine/buckets
pow-2 buckets, serving-reachable templates only, hot shapes first) and
drives each engine at each shape so the XLA persistent cache fills with
exactly the executables the serving set needs. A later daemon boot —
or `make prewarm` on a deploy host — then answers its first request
from the cache: the compile wall is paid once per host+toolchain.

Usage:
    python scripts/prewarm.py --schemes eddsa --max-b 64   # warm
    python scripts/prewarm.py --list                       # print work-list, no jax
    python scripts/prewarm.py --check                      # warmcheck gate, no jax

`--check` (the `make warmcheck` gate) verifies manifest enumeration ==
surface knobs × buckets with no silent gaps — pure stdlib, sub-second,
no backend import.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_ROOT))

from mpcium_tpu.warm import manifest as wm  # noqa: E402


def _build(args):
    surface = wm.load_default_surface()
    knobs = wm.default_knobs(args.threshold)
    schemes = None
    if args.schemes:
        schemes = tuple(s.strip() for s in args.schemes.split(",") if s.strip())
    buckets = wm.BUCKETS
    if args.buckets:
        buckets = tuple(
            int(b) for b in args.buckets.split(",") if b.strip()
        )
    traffic = wm.load_traffic(
        args.ledger or os.path.join(str(_ROOT), "COMPILE_LEDGER.json")
    )
    return wm.build_manifest(
        surface, knobs, buckets=buckets, schemes=schemes,
        max_b=args.max_b, traffic=traffic,
    ), surface, knobs


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--schemes", default="",
                   help="comma list of eddsa,ecdsa,dkg,reshare (default all)")
    p.add_argument("--max-b", type=int, default=None,
                   help="largest batch bucket to warm (default: all 14)")
    p.add_argument("--buckets", default="",
                   help="explicit comma list of pow-2 buckets")
    p.add_argument("--threshold", "--q", type=int, default=None, dest="threshold",
                   help="mpc threshold t (warm quorum q = t+1; default 1)")
    p.add_argument("--budget-s", type=float, default=1800.0,
                   help="wall-clock budget; remaining entries are skipped")
    p.add_argument("--cache-dir", default="",
                   help="XLA persistent cache dir (default: "
                        "<checkout>/.jax_cache; JAX_COMPILATION_CACHE_DIR "
                        "wins over both)")
    p.add_argument("--ledger", default="",
                   help="COMPILE_LEDGER.json for traffic priority")
    p.add_argument("--out", default="",
                   help="report dir for WARM_MANIFEST.json "
                        "(default: the cache dir)")
    p.add_argument("--list", action="store_true",
                   help="print the work-list and exit (no jax import)")
    p.add_argument("--check", action="store_true",
                   help="verify enumeration covers knobs × buckets with "
                        "no gaps; exit 1 on any problem (no jax import)")
    args = p.parse_args(argv)

    if args.check:
        surface = wm.load_default_surface()
        problems = wm.coverage_check(surface, wm.default_knobs(args.threshold))
        for prob in problems:
            print(f"WARM GAP: {prob}")
        man = wm.build_manifest(surface, wm.default_knobs(args.threshold))
        print(
            f"warmcheck: {man['counts']['entries']} signatures over "
            f"{man['counts']['serving_templates']} serving templates × "
            f"{man['counts']['buckets']} buckets — "
            f"{len(problems)} problem(s)"
        )
        return 1 if problems else 0

    manifest, _surface, _knobs = _build(args)
    if args.list:
        for e in manifest["entries"]:
            print(f"{e['engine']:16s} {e['shape']:32s} "
                  f"priority={e['priority']:.1f}")
        print(f"{manifest['counts']['entries']} entries")
        return 0

    # jax from here on: configure the cache, then walk
    from mpcium_tpu.warm import prewarm as pw

    cache_dir = pw.configure_cache(args.cache_dir or None)
    report = pw.prewarm(
        manifest, args.budget_s, report_dir=args.out or cache_dir,
        aot_store=None,
    )
    t = report["totals"]
    print(json.dumps(t, indent=1, sort_keys=True))
    if t["unpredicted"]:
        print(
            f"WARNING: {t['unpredicted']} warmed shape(s) were NOT in "
            f"COMPILE_SURFACE.json — static surface drift; run "
            f"python scripts/mpcshape_surface.py"
        )
    print(f"report: {report.get('path', '(unwritten)')}")
    return 0 if t["failed"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
