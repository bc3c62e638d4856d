"""A traced CPU rehearsal of the cell ``secp-2of3-paillier.gg18-waves`` at
its scheme file's tiny size (a wave of 2, 1024-bit fixtures, shrunk proof
domains), the profiler's trace stood in for by a recorded device plane
that holds GG18 round programs: the names under ``metrics`` EQUAL the
names the manifest gives the cell (the sixteen entries with no list, the
cell's own five), over at least two counted waves. Its untraced rehearsal
is test_bench_rehearsal.py's ``test_rehearsal_of_each_cell``. Slow tier:
the GG18 programs compile for tens of minutes on XLA:CPU.

Runs via the subprocess wrapper of the other distributed-GG18 suites
(tests/test_gg18_batch_party.py says why): on some hosts XLA:CPU aborts
compiling the party's graphs, and MPCIUM_XFAIL_XLA_CRASH=1 (opt-in,
known-bad hosts only) downgrades exactly that crash to xfail."""
import os

import pytest
from conftest import run_isolated
from test_bench_rehearsal import (  # noqa: F401 — steer is a fixture
    _RecordedTracer, _manifest, _run, steer)

from benchmark import harness, peaks

pytestmark = pytest.mark.slow

CELL = "secp-2of3-paillier.gg18-waves"
_INNER = os.environ.get("MPCIUM_BENCH_GG18_INNER")


def test_a_traced_gg18_rehearsal_isolated():
    if _INNER:
        pytest.skip("wrapper entry; inner run executes the real test")
    run_isolated(__file__, "test_a_traced_rehearsal_prints_the_cells_names",
                 "MPCIUM_BENCH_GG18_INNER")


class _GG18Tracer(_RecordedTracer):
    """The recorded device plane with its two programs named as GG18 round
    programs (an MtA one and a curve one)."""

    def finish(self, run_data):
        super().finish(run_data)
        modules = run_data.trace["planes"][1]["lines"][0]["events"]
        modules[0][0] = "jit_gg18_r2_respond(1)"
        modules[1][0] = "jit_gg18_r5a_commit(2)"


@pytest.mark.skipif(not _INNER, reason="runs via the subprocess wrapper")
def test_a_traced_rehearsal_prints_the_cells_names(
        steer, capsys, monkeypatch):  # noqa: F811
    monkeypatch.setattr(harness, "Tracer", _GG18Tracer)
    # the CPU has no published peak: the chip's stands in for the share
    v5e = peaks.for_device("TPU v5 lite")
    monkeypatch.setattr(peaks, "for_device", lambda kind: v5e)
    # long enough for the second counted wave once the first has run
    rc, lines = _run(steer, capsys, CELL, trace=1, seconds=600.0)
    last = lines[-1]
    rows = [ln for ln in lines if ln.get("phase") == "check"][0]["compared"]
    assert rc == 0 and last["correct"] is True, rows
    waves = [ln for ln in lines if ln.get("phase") == "wave"]
    assert sum(1 for w in waves if w["measured"]) >= 2
    assert all(w["compile_requests"] == 0 and w["succeeded"] == 2
               for w in waves)
    assert rows["party_shapes"]["value"] == ["B2|q3"]
    assert rows["high_s_signatures"]["value"] == 0
    cell = harness.Cell(str(steer), CELL)
    want = {m["name"] for m in cell.metrics("per_layer")}
    assert set(last["metrics"]) == want and len(want) == 21
    m = {k: v["value"] for k, v in last["metrics"].items()}
    for name in ("gg18.phase_ms_per_wave", "gg18.wire_ms_per_wave",
                 "gg18.achieved_gops", "gg18.mxu_roofline_pct"):
        assert m[name] > 0, name
    assert m["gg18.mta_device_share_pct"] == pytest.approx(50.0)
    assert {"busy_s", "window_s"} <= set(last["device"])
