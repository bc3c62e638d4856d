"""Bench-trajectory ledger (perf/ledger.py + perf/report.py): every
committed artifact normalizes, degraded runs segregate from chip trends,
and the dashboard/counter-track renderers are deterministic and
schema-valid."""
import json
import os

import pytest

from mpcium_tpu.perf import ledger, report
from mpcium_tpu.perf.envfp import env_fingerprint
from mpcium_tpu.trace.export import chrome_trace
from mpcium_tpu.trace.schema import validate_chrome

pytestmark = pytest.mark.perf

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_discovers_every_committed_artifact():
    names = {os.path.basename(p) for p in ledger.discover_artifacts(ROOT)}
    expected = (
        {f"BENCH_r0{i}.json" for i in range(3, 6)}
        | {f"MULTICHIP_r0{i}.json" for i in range(2, 6)}
        | {"SOAK_r01.json", "BENCH_TPU_LATEST.json", "BENCH_TPU_OT.json"}
    )
    assert expected <= names


def test_every_committed_artifact_normalizes():
    for path in ledger.discover_artifacts(ROOT):
        rec = ledger.normalize(path)  # raises = gate failure
        assert rec["kind"] in (
            "bench", "soak", "multichip", "pipeline", "campaign")
        assert rec["fingerprint"], path
        assert isinstance(rec["metrics"], dict)


def test_dnf_rounds_are_degraded_with_notes(tmp_path):
    # a crashed round (rc=1, nothing parsed), written here; the committed
    # BENCH_r04.json is the watchdog-timeout DNF (rc=124)
    crashed = tmp_path / "BENCH_r98.json"
    crashed.write_text(json.dumps({
        "n": 98, "cmd": "python bench.py", "rc": 1,
        "tail": "Traceback (most recent call last):\n", "parsed": None,
    }))
    for path, rc in ((str(crashed), 1),
                     (os.path.join(ROOT, "BENCH_r04.json"), 124)):
        rec = ledger.normalize(path)
        assert rec["degraded"]
        assert rec["context"]["rc"] == rc
        assert any("DNF" in n for n in rec["notes"])
        assert not rec["metrics"]


def test_b_sweep_entries_ingest_without_string_sniffing():
    """bench.py's sweep contract: numeric entries become metrics, the
    structured DNF shape {"dnf": true, "reason": ...} becomes a note,
    and anything else (legacy bare strings) is flagged verbatim — the
    ledger never parses prose to classify an entry."""
    rec = ledger._base_record("BENCH_synthetic.json", "bench")
    ledger._normalize_bench_parsed(rec, {
        "metric": "m", "value": 1.0, "platform": "tpu",
        "b_sweep": {
            "1024": 39.7,
            "8192": {"dnf": True, "reason": "watchdog fired"},
            "16384": "DNF: legacy prose entry",
        },
    })
    assert rec["metrics"]["b_sweep_1024_sigs_per_sec"] == 39.7
    assert rec["context"]["b_sweep"]["1024"] == 39.7
    assert rec["context"]["b_sweep"]["8192"] == {"dnf": True}
    assert any(
        "b_sweep B=8192 DNF: watchdog fired" in n for n in rec["notes"]
    )
    assert any("unstructured" in n and "16384" in n for n in rec["notes"])
    assert "b_sweep_8192_sigs_per_sec" not in rec["metrics"]
    assert "b_sweep_16384_sigs_per_sec" not in rec["metrics"]


def test_committed_ot_artifact_b_sweep_is_structured():
    """BENCH_TPU_OT.json's B=8192 DNF was migrated to the structured
    shape: it must normalize to a DNF note, not an unstructured flag."""
    rec = ledger.normalize(os.path.join(ROOT, "BENCH_TPU_OT.json"))
    assert rec["metrics"]["b_sweep_4096_sigs_per_sec"] == 72.091
    assert any("b_sweep B=8192 DNF" in n for n in rec["notes"])
    assert not any("unstructured" in n for n in rec["notes"])


def test_cpu_fallback_rounds_never_look_like_chip_records():
    r5 = ledger.normalize(os.path.join(ROOT, "BENCH_r05.json"))
    chip = ledger.normalize(os.path.join(ROOT, "BENCH_TPU_LATEST.json"))
    assert r5["degraded"] and not chip["degraded"]
    assert r5["fingerprint"] != chip["fingerprint"]
    # the stale-fallback rider is noted, and its chip number did NOT
    # become this record's metric
    assert any("last_tpu_measurement" in n for n in r5["notes"])
    assert r5["metrics"]["secp256k1_2of3_gg18_sigs_per_sec"] < 1.0


def test_soak_without_env_stamp_groups_as_unstamped():
    rec = ledger.normalize(os.path.join(ROOT, "SOAK_r01.json"))
    assert rec["kind"] == "soak"
    assert rec["fingerprint"].endswith("/unstamped")
    assert rec["metrics"]["sigs_per_s"] > 0
    assert "latency_overall_p99_ms" in rec["metrics"]
    assert rec["context"]["accounting_ok"] is True


def test_soak_with_env_stamp_groups_by_platform(tmp_path):
    doc = {
        "throughput": {"duration_s": 10.0, "sigs_per_s": 5.0,
                       "sigs_per_s_under_slo": 4.0, "slo_hit_rate": 0.8},
        "outcomes": {"submitted": 50, "succeeded": 50, "shed": 0,
                     "failed": 0, "retries": 0},
        "latency_ms": {"overall": {"p50": 100.0, "p99": 900.0}},
        "accounting_ok": True,
        "env": env_fingerprint(),
    }
    p = tmp_path / "SOAK_r99.json"
    p.write_text(json.dumps(doc))
    rec = ledger.normalize(str(p))
    assert not rec["fingerprint"].endswith("/unstamped")
    assert rec["platform"] == doc["env"]["platform"]


def test_multichip_ok_vs_failed(tmp_path):
    failed = tmp_path / "MULTICHIP_r99.json"
    failed.write_text(json.dumps({
        "n_devices": 8, "rc": 1, "ok": False, "skipped": False,
        "tail": "Traceback (most recent call last):\n",
    }))
    r1 = ledger.normalize(str(failed))
    r2 = ledger.normalize(os.path.join(ROOT, "MULTICHIP_r02.json"))
    assert r1["metrics"]["dryrun_ok"] == 0.0 and r1["degraded"]
    assert r2["metrics"]["dryrun_ok"] == 1.0 and not r2["degraded"]


def test_history_roundtrip_and_determinism(tmp_path):
    records = ledger.build_history(ROOT)
    assert len(records) >= 12
    path = str(tmp_path / "hist.jsonl")
    ledger.write_history(records, path)
    assert ledger.load_history(path) == records
    # a second build is byte-identical: no wall clock, no host state
    again = ledger.build_history(ROOT)
    assert again == records


def test_group_by_fingerprint_segregates_degraded_from_chip():
    groups = ledger.group_by_fingerprint(ledger.build_history(ROOT))
    for key, recs in groups.items():
        kinds = {r["degraded"] for r in recs if r["kind"] == "bench"}
        # within one bench fingerprint group, degraded status is uniform
        # (a chip trend never averages a CPU fallback)
        assert len(kinds) <= 1, key


def test_dashboard_renders_all_sections_deterministically():
    records = ledger.build_history(ROOT)
    d1 = report.render_dashboard(records)
    d2 = report.render_dashboard(records)
    assert d1 == d2
    for heading in ("## Flagship trajectory — on-chip",
                    "## Bench rounds — degraded / DNF",
                    "## Soak (serving under SLO)",
                    "## Multichip dryruns"):
        assert heading in d1
    # the degraded table and the chip table never share a row
    assert "BENCH_r05.json" in d1 and "BENCH_TPU_LATEST.json" in d1


def test_counter_track_merges_into_valid_chrome_trace():
    records = ledger.build_history(ROOT)
    extra = report.counter_track(records)
    assert any(e["ph"] == "C" for e in extra)
    assert all(e["pid"] == report.COUNTER_PID
               for e in extra if e["ph"] == "C")
    spans = [{
        "name": "phase:x", "trace_id": "t" * 16, "span_id": "s" * 16,
        "parent_id": None, "node": "node0", "tid": "main",
        "t0_ns": 0, "t1_ns": 1000, "kind": "X", "attrs": {},
    }]
    doc = chrome_trace({"node0": (spans, 0)}, extra_events=extra)
    n = validate_chrome(doc)
    assert n == len(doc["traceEvents"])
    # degraded bench records contribute NO counter samples
    degraded_sources = {r["source"] for r in records
                        if r["kind"] == "bench" and r["degraded"]}
    assert degraded_sources  # the committed set has them
    chip_points = [e for e in extra if e["ph"] == "C"]
    bench_chip = [r for r in records
                  if r["kind"] == "bench" and not r["degraded"]]
    assert len(chip_points) >= len(bench_chip)
