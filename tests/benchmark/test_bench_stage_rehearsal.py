"""CPU rehearsal of a traced run of an accepted cell at the tiny size of
test_bench_rehearsal.py (its fixtures; the profiler's trace stood in
for): the result line carries PR 25's ten stage metrics beside the eight
the benchmark had, read from the program's own spans and counters."""
import pytest
from test_bench_rehearsal import (  # noqa: F401 — steer is a fixture
    _RecordedTracer, _cells, _run, steer)

from benchmark import harness

OLD = {"client.submit_ms_per_sign", "scheduler.batch_fill_ratio",
       "scheduler.dispatch_age_ms", "party.phase_ms_per_wave",
       "cluster.wave_growth_pct", "kernels.device_ms_per_wave",
       "kernels.achieved_gops", "device.idle_share_pct"}
NEW = {"client.enqueue_ms_per_sign", "transport.queue_wait_ms",
       "bridge.inflight_peak", "intake.handle_ms_per_sign",
       "batch.admit_ms_per_wave", "batch.prepare_ms_per_wave",
       "session.wire_ms_per_wave", "egress.result_ms_per_wave",
       "host.unnamed_idle_pct", "trace.spans_dropped"}


def test_a_traced_run_of_an_accepted_cell_prints_the_ten_stage_metrics(
        steer, capsys, monkeypatch):
    monkeypatch.setattr(harness, "Tracer", _RecordedTracer)
    rc, lines = _run(steer, capsys, _cells()[0], trace=1)
    last = lines[-1]
    assert rc == 0 and last["correct"] is True
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert NEW <= set(m) <= NEW | OLD
    assert m["trace.spans_dropped"] == 0
    assert 1 <= m["bridge.inflight_peak"] <= 8  # a wave of 8, three bridges
    assert 0 <= m["host.unnamed_idle_pct"] <= 100
    for name in NEW - {"trace.spans_dropped", "bridge.inflight_peak",
                       "host.unnamed_idle_pct"}:
        assert m[name] > 0, name
    # a stage is part of its wave, and the stages named come to less
    waves = [ln for ln in lines if ln.get("phase") == "wave"
             and ln["measured"]]
    wave_ms = max(w["seconds"] for w in waves) * 1e3
    for name in ("batch.admit_ms_per_wave", "batch.prepare_ms_per_wave",
                 "session.wire_ms_per_wave", "egress.result_ms_per_wave"):
        assert m[name] < wave_ms, name
    assert m["client.enqueue_ms_per_sign"] < m["client.submit_ms_per_sign"]
    gaps = dict(last["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        last["device"]["window_s"] * 0.9, rel=0.01)
