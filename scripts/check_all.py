#!/usr/bin/env python3
"""One-pass static gate: mpclint + mpcflow + mpcshape + artifact drift.

Parses the project AST exactly once (analysis/core.parse_project) and
hands the same ParsedFile list to all three analyzers — this is the
shared AST cache ``make check`` runs. Findings from all of them gate
against the one .mpclint-baseline.json (fail-closed both ways: new
findings fail AND stale entries fail), and the committed
HOST_TRANSFER_BUDGET.json and COMPILE_SURFACE.json must match their
sweeps byte-for-byte.

Exit codes: 0 clean, 1 violations/drift, 2 operator error.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_ROOT))

from mpcium_tpu.analysis.baseline import (  # noqa: E402
    DEFAULT_BASELINE,
    BaselineError,
    load_baseline,
)
from mpcium_tpu.analysis.core import lint_parsed, parse_project  # noqa: E402
from mpcium_tpu.analysis.flow import build_budget, run_flow_parsed  # noqa: E402
from mpcium_tpu.analysis.rules import all_rules  # noqa: E402
from mpcium_tpu.analysis.shape import (  # noqa: E402
    SURFACE_BASENAME,
    run_shape_parsed,
)
from mpcium_tpu.analysis.shape import render as render_surface  # noqa: E402

from mpcflow_budget import BUDGET_FILE, render  # noqa: E402


def main(argv=None) -> int:
    out = sys.stdout
    t0 = time.monotonic()

    # one parse, three analyzers
    files, parse_errors = parse_project([_ROOT / "mpcium_tpu"], root=_ROOT)
    lint_result = lint_parsed(files, all_rules(), parse_errors=parse_errors)
    flow_result, sites = run_flow_parsed(files)
    shape_result, surface = run_shape_parsed(files)
    findings = (
        lint_result.findings + flow_result.findings + shape_result.findings
    )

    for err in parse_errors:
        out.write(f"PARSE ERROR: {err}\n")

    baseline_path = _ROOT / DEFAULT_BASELINE
    try:
        baseline = load_baseline(baseline_path)
    except BaselineError as e:
        out.write(f"BASELINE ERROR: {e}\n")
        return 2
    new, grandfathered, stale = baseline.split(findings)

    for f in new:
        out.write(f.render() + "\n")
    for fp in stale:
        out.write(
            f"STALE BASELINE ENTRY: {fp} — the finding no longer fires; "
            f"delete it from {baseline_path.name}\n"
        )

    budget_path = _ROOT / BUDGET_FILE
    budget_text = render(build_budget(sites))
    drifted = not budget_path.exists() or budget_path.read_text() != budget_text
    if drifted:
        out.write(
            f"BUDGET DRIFT: {BUDGET_FILE} does not match the sweep — "
            f"regenerate with scripts/mpcflow_budget.py and review the diff\n"
        )

    surface_path = _ROOT / SURFACE_BASENAME
    surface_text = render_surface(surface)
    surface_drifted = (
        not surface_path.exists()
        or surface_path.read_text() != surface_text
    )
    if surface_drifted:
        out.write(
            f"SURFACE DRIFT: {SURFACE_BASENAME} does not match the sweep — "
            f"regenerate with scripts/mpcshape_surface.py and review the diff\n"
        )

    # warmcheck off the same sweep's surface: the pre-warm work-list
    # (mpcium_tpu.warm.manifest) must enumerate exactly knobs × buckets —
    # a gap here means a serving shape the boot-time warm pass would
    # silently never compile
    from mpcium_tpu.warm.manifest import coverage_check, default_knobs

    warm_problems = coverage_check(surface, default_knobs())
    for prob in warm_problems:
        out.write(f"WARM GAP: {prob}\n")

    elapsed = time.monotonic() - t0
    out.write(
        f"check_all: {len(files)} files in {elapsed:.2f}s — "
        f"{len(new)} new, {len(grandfathered)} grandfathered, "
        f"{len(stale)} stale, budget "
        f"{'DRIFTED' if drifted else 'in sync'}, surface "
        f"{'DRIFTED' if surface_drifted else 'in sync'}, warm manifest "
        f"{f'{len(warm_problems)} GAP(S)' if warm_problems else 'covered'}\n"
    )
    return 1 if (
        new or stale or parse_errors or drifted or surface_drifted
        or warm_problems
    ) else 0


if __name__ == "__main__":
    raise SystemExit(main())
