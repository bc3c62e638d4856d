"""Tier-1 perf-observatory gate: `make perfcheck` passes on HEAD, the
committed history/dashboard match a regeneration (drift gate, same
contract as HOST_TRANSFER_BUDGET.json), and the gate demonstrably fails
on an injected slowdown — proven against a freshly measured
self-baseline so the assertion holds on any host."""
import json
import os
import subprocess
import sys

import pytest

from mpcium_tpu.perf import ledger, microbench, report, statcheck

pytestmark = pytest.mark.perf

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "scripts"))

import perfcheck  # noqa: E402


def test_perfcheck_main_passes_on_head():
    # strict on the baseline's host, informational elsewhere — either
    # way HEAD must exit 0 (this IS the tier-1 regression gate).
    # Measured in a fresh subprocess so the samples share a process
    # context with the committed baseline (--update-baseline measures
    # standalone): hundreds of tests into a shared pytest process the
    # thread-handoff rows inflate ~2x on a contended 1-core host and
    # flag regressions in code that did not change.
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "perfcheck.py")],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
    )
    assert r.returncode == 0, r.stdout + r.stderr


def test_committed_baseline_exists_and_has_all_benches():
    with open(os.path.join(ROOT, "PERF_baseline_micro.json")) as f:
        doc = json.load(f)
    assert set(doc["benches"]) == set(microbench.ALL_BENCHES)
    for name, b in doc["benches"].items():
        assert len(b["samples"]) >= 8, name
        assert all(v > 0 for v in b["samples"]), name
    assert doc["host"]


def test_committed_history_matches_regeneration():
    committed = ledger.load_history(
        os.path.join(ROOT, ledger.HISTORY_FILE)
    )
    regenerated = ledger.build_history(ROOT)
    assert committed == regenerated, (
        "PERF_history.jsonl drifted from the committed artifacts — "
        "run `python scripts/perfcheck.py --regen-history`"
    )
    sources = {r["source"] for r in committed}
    for i in range(3, 6):
        assert f"BENCH_r0{i}.json" in sources
    for i in range(2, 6):
        assert f"MULTICHIP_r0{i}.json" in sources
    assert "SOAK_r01.json" in sources


def test_committed_dashboard_matches_regeneration():
    with open(os.path.join(ROOT, "PERFORMANCE_dashboard.md")) as f:
        committed = f.read()
    with open(os.path.join(ROOT, "PERF_baseline_micro.json")) as f:
        baseline = json.load(f)
    regenerated = report.render_dashboard(
        ledger.build_history(ROOT), micro_baseline=baseline
    )
    assert committed == regenerated, (
        "PERFORMANCE_dashboard.md drifted — run "
        "`python scripts/perfcheck.py --regen-history`"
    )


def test_gate_fails_on_injected_slowdown_vs_self_baseline():
    # host-independent proof of gate mechanics: measure a baseline NOW,
    # inject 1.5x on a second measurement of the same bench
    base = microbench.field_mulmod(samples=15)
    cur = [v * 1.5 for v in microbench.field_mulmod(samples=15)]
    v = statcheck.compare("field_mulmod", base, cur)
    assert v.regressed, v.render()
    # and the unscaled re-measurement passes
    v2 = statcheck.compare("field_mulmod", base,
                           microbench.field_mulmod(samples=15))
    assert not v2.regressed, v2.render()


def test_perfcheck_inject_slowdown_exits_nonzero():
    # through the CLI path (retry-once included): only asserted strictly
    # when this host matches the committed baseline, because a foreign
    # host is informational by design
    with open(os.path.join(ROOT, "PERF_baseline_micro.json")) as f:
        doc = json.load(f)
    from mpcium_tpu.perf.envfp import host_fingerprint

    rc = perfcheck.main(["--inject-slowdown", "4.0", "--samples", "12"])
    if doc["host"] == host_fingerprint():
        assert rc == 1
    else:
        assert rc == 0  # informational on a foreign host, never fails
