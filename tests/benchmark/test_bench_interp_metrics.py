"""The eight readers PR 40 adds under benchmark/layer_metrics/ for the
program's interpreter account, each fed hand-made start and end snapshots
and spans (test_bench_stage_readers.py's helpers) and checked against a
number worked out by hand; ``None`` on a program that lacks the gauges,
the counter, the histogram or the attribute. No JAX, no chip."""
import json
import os
from types import SimpleNamespace

import pytest
from test_bench_rehearsal import (  # noqa: F401 — steer is a fixture
    _RecordedTracer, _cells, _run, steer)
from test_bench_stage_readers import (MS, WINDOW, hist, reader, snapshot,
                                      span)

from benchmark import harness, interp_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NEW = ["interp.cpu_cores", "interp.cpu_ms_per_sign",
       "client.submit_cpu_ms_per_sign", "transport.worker_cpu_ms_per_sign",
       "batch.thread_cpu_ms_per_wave", "host.stage_on_cpu_pct",
       "interp.handover_lag_ms", "log.ms_per_sign"]


def make_run(spans, start, end, signs_a_wave=4, quorum=2):
    """A RunData as harness.measure makes one: an unmeasured wave and two
    measured ones of ``signs_a_wave`` requests, a window of 10 s."""
    waves = [SimpleNamespace(measured=m, requests=[object()] * signs_a_wave)
             for m in (False, True, True)]
    served = SimpleNamespace(config={}, wave_size=signs_a_wave, quorum=quorum,
                             scheme=None, metrics_snapshot=lambda: end)
    return harness.RunData(
        served, {"waves": waves, "window_start_ns": WINDOW,
                 "window_end_ns": WINDOW + 10_000 * MS}, start, spans)


def account(counters=None, **cpu_s):
    """A first node's snapshot: ``interp.cpu_s.<role>`` gauges (a role's
    ``-`` written ``_`` in the keyword) and the log counters."""
    snap = snapshot(gauges={
        f"interp.cpu_s.{role.replace('_', '-')}": v
        for role, v in cpu_s.items()})
    snap["counters"] = counters or {}
    return snap


@pytest.fixture()
def run():
    spans = [
        # before the window: never read
        span("client:submit", "client", -500, 50, tx="w", cpu_s=0.040),
        span("host:batch_prepare", "a", -400, 100, cpu_s=0.1),
        # the SDK: 0.2 and 0.4 ms of CPU; a third span without the attribute
        span("client:submit", "client", 0, 5, tx="x", sign_s=0.002,
             cpu_s=0.0002),
        span("client:submit", "client", 6, 4, tx="y", sign_s=0.003,
             cpu_s=0.0004),
        span("client:submit", "client", 12, 4, tx="z", sign_s=0.003),
        # the stages: 10 of 10 ms capped from 12, 50 of 100, 2 of 8, and 30
        # of 200 on the other node: 92 of 318 ms; one stage without cpu_s
        span("host:manifest_admit", "a", 10, 10, cpu_s=0.012),
        span("host:batch_prepare", "a", 30, 100, cpu_s=0.050),
        span("host:result_egress", "a", 900, 8, cpu_s=0.002),
        span("host:batch_prepare", "b", 30, 200, cpu_s=0.030),
        span("host:result_egress", "b", 900, 4),
        # not a stage of the three
        span("host:quorum_select", "a", 29, 1, cpu_s=0.001),
        span("phase:bsign_nonce_commit", "a", 300, 50, cpu_s=0.005),
    ]
    start = {
        "a": account({"log.emit_s_total": 1.0, "log.lines_total": 100.0},
                     main=2.0, loopback=3.0, loopback_q=1.0, bsign=0.5),
        "b": snapshot(),
    }
    start["a"]["histograms"] = {"interp.handover_lag_s": hist(0.10, 100)}
    end = {
        "a": account({"log.emit_s_total": 1.016, "log.lines_total": 180.0},
                     main=4.0, loopback=7.0, loopback_q=2.5, bsign=1.3,
                     send=0.2),  # a role the start had not seen yet
        "b": snapshot(),
    }
    end["a"]["histograms"] = {"interp.handover_lag_s": hist(0.55, 200)}
    return make_run(spans, start, end)


@pytest.mark.parametrize("name,by_hand", [
    # main 2.0 + loopback 4.0 + loopback-q 1.5 + bsign 0.8 + send 0.2 =
    # 8.5 s of CPU in a window of 10 s
    ("interp.cpu_cores", 0.85),
    # ... among 8 requests
    ("interp.cpu_ms_per_sign", 1062.5),
    # (0.2 + 0.4) / 2 spans that carry it
    ("client.submit_cpu_ms_per_sign", 0.3),
    # (4.0 + 1.5) s among 8 requests
    ("transport.worker_cpu_ms_per_sign", 687.5),
    # 0.8 s among 2 signing nodes and 2 measured waves
    ("batch.thread_cpu_ms_per_wave", 200.0),
    # (10 + 50 + 2 + 30) ms on a CPU of (10 + 100 + 8 + 200) ms of stage
    ("host.stage_on_cpu_pct", 92 / 318 * 100),
    # (0.55 - 0.10) s over (200 - 100) wake-ups
    ("interp.handover_lag_ms", 4.5),
    # 16 ms among 8 requests
    ("log.ms_per_sign", 2.0),
])
def test_each_reader_gives_the_value_worked_out_by_hand(run, name, by_hand):
    assert reader(name)(run) == pytest.approx(by_hand)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_on_a_program_without_the_account(name):
    """The parent's traced run: registries with none of the gauges, the
    counter or the histogram, spans without ``cpu_s``. Nothing raises."""
    old = [span("client:submit", "client", 0, 5, tx="x", sign_s=0.002),
           span("host:batch_prepare", "a", 30, 100),
           span("host:result_egress", "a", 900, 8, enqueue_s=0.004)]
    plain = {"a": snapshot(gauges={"trace.dropped_spans": 0.0}),
             "b": snapshot()}
    assert reader(name)(make_run(old, plain, plain)) is None
    assert reader(name)(make_run([], {}, {})) is None


def test_a_stage_shorter_than_the_clocks_step_is_capped_at_its_duration():
    """One tick of a 10 ms thread clock on a 3 ms stage: the share stays
    at 100, never above."""
    spans = [span("host:manifest_admit", "a", 10, 3, cpu_s=0.010)]
    assert reader("host.stage_on_cpu_pct")(
        make_run(spans, {}, {})) == pytest.approx(100.0)


def test_the_shared_arithmetic_counts_one_process_once_and_no_signs_as_none():
    start = {"a": account(main=1.0), "b": snapshot()}
    end = {"a": account(main=3.0, loopback=1.0), "b": snapshot()}
    run = make_run([], start, end)
    assert interp_reduce.cpu_delta_s(run) == pytest.approx(3.0)
    assert interp_reduce.cpu_delta_s(run, ["loopback"]) == pytest.approx(1.0)
    assert interp_reduce.cpu_delta_s(run, ["tcpbus"]) is None
    assert interp_reduce.counter_delta(run, "log.emit_s_total") is None
    assert interp_reduce.per_sign_ms(run, 2.0) == pytest.approx(250.0)
    assert interp_reduce.per_sign_ms(run, None) is None
    assert interp_reduce.per_sign_ms(
        make_run([], start, end, signs_a_wave=0), 2.0) is None


def test_the_manifest_appends_the_eight_entries_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    last = manifest["per_layer"][-8:]
    assert [m["name"] for m in last] == NEW
    layers = {m["layer"] for m in manifest["per_layer"][:-8]}
    ends = {m["name"] for m in manifest["end_to_end"]}
    for m in last:
        # no list of cells: the account is the process's, whatever the cell
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}
        assert m["layer"] in layers and m["moves"] in ends
        assert m["source"] in ("program_counter", "program_span")
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
    assert {m["name"]: (m["unit"], m["better"]) for m in last} == {
        "interp.cpu_cores": ("cores", "lower"),
        "interp.cpu_ms_per_sign": ("ms", "lower"),
        "client.submit_cpu_ms_per_sign": ("ms", "lower"),
        "transport.worker_cpu_ms_per_sign": ("ms", "lower"),
        "batch.thread_cpu_ms_per_wave": ("ms", "lower"),
        "host.stage_on_cpu_pct": ("%", "higher"),
        "interp.handover_lag_ms": ("ms", "lower"),
        "log.ms_per_sign": ("ms", "lower")}
    for cell in (w["name"] for w in manifest["workloads"]):
        names = [m["name"] for m in harness.Cell(ROOT, cell).metrics(
            "per_layer")]
        assert names[-8:] == NEW, cell


def test_a_traced_run_of_an_accepted_cell_prints_the_eight(
        steer, capsys, monkeypatch):  # noqa: F811
    """A CPU rehearsal (no device number is read from it): every new
    metric is in the line, a value and not None, within what it can be."""
    monkeypatch.setattr(harness, "Tracer", _RecordedTracer)
    rc, lines = _run(steer, capsys, _cells()[0], trace=1, seconds=4.0)
    last = lines[-1]
    assert rc == 0 and last["correct"] is True
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert set(NEW) <= set(m)
    assert 0 < m["interp.cpu_cores"] < 64
    assert 0 < m["client.submit_cpu_ms_per_sign"] < m["interp.cpu_ms_per_sign"]
    assert 0 < m["transport.worker_cpu_ms_per_sign"] < (
        m["interp.cpu_ms_per_sign"])
    assert m["batch.thread_cpu_ms_per_wave"] > 0
    assert 0 < m["host.stage_on_cpu_pct"] <= 100
    assert m["interp.handover_lag_ms"] >= 0
    assert m["log.ms_per_sign"] > 0
    assert m["trace.spans_dropped"] == 0
