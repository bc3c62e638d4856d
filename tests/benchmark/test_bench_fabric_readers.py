"""The reader PR 26 adds under benchmark/layer_metrics/, fed hand-made
runs as in test_bench_stage_readers.py (its helpers) and checked against
numbers worked out by hand; ``None`` on a program that lacks the
attribute; its manifest entry; and a CPU rehearsal of a traced run of an
accepted cell that prints it."""
import json
import os

import pytest
from test_bench_rehearsal import (  # noqa: F401 — steer is a fixture
    _RecordedTracer, _cells, _run, steer)
from test_bench_stage_readers import make_run, reader, snapshot, span

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ENQUEUE = "egress.enqueue_ms_per_wave"


def test_enqueue_ms_is_the_attribute_summed_a_node_and_wave():
    spans = [
        span("host:result_egress", "a", -400, 999, enqueue_s=0.5),  # before
        span("host:result_egress", "a", 900, 8, enqueue_s=0.006),
        span("host:result_egress", "a", 5900, 8, enqueue_s=0.004),
        span("host:result_egress", "b", 900, 4, enqueue_s=0.003),
        span("host:result_egress", "b", 5900, 4, enqueue_s=0.001),
        span("host:batch_prepare", "a", 30, 100, enqueue_s=9.0),  # not egress
    ]
    # 14 ms over two nodes and two waves
    assert reader(ENQUEUE)(make_run(spans, {}, {})) == pytest.approx(3.5)


def test_enqueue_ms_counts_only_the_spans_that_carry_the_attribute():
    """A node whose egress span lacks it (an older program's) is not one
    of the nodes the sum is shared among."""
    spans = [
        span("host:result_egress", "a", 900, 8, enqueue_s=0.006),
        span("host:result_egress", "a", 5900, 8, enqueue_s=0.004),
        span("host:result_egress", "b", 900, 4),
    ]
    assert reader(ENQUEUE)(make_run(spans, {}, {})) == pytest.approx(5.0)


def test_the_enqueue_reader_finds_nothing_on_a_program_without_its_source():
    """An older program: egress spans without the attribute (PR 24's had
    none). Nothing raises."""
    old = [span("host:result_egress", "a", 900, 8),
           span("session", "a", 200, 800)]
    plain = {"a": snapshot()}
    assert reader(ENQUEUE)(make_run(old, plain, plain)) is None
    assert reader(ENQUEUE)(make_run([], {}, {})) is None


def test_the_manifest_lists_the_enqueue_metric_for_the_accepted_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    cells = [w["name"] for w in manifest["workloads"]][:3]
    assert manifest["per_layer"][-1]["name"] == ENQUEUE
    assert entries[ENQUEUE] == {
        "name": ENQUEUE, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "transport and bridge",
        "moves": "sign_latency_p95_ms", "workloads": cells}
    # the layer's name is one the manifest already had
    assert entries["transport.queue_wait_ms"]["layer"] == (
        entries[ENQUEUE]["layer"])
    # every entry has its reader's file, and every reader its entry
    files = {f[:-3] for f in os.listdir(
        os.path.join(ROOT, "benchmark", "layer_metrics")) if f.endswith(".py")}
    assert files == set(entries)


def test_a_traced_run_of_an_accepted_cell_prints_the_enqueue_metric(
        steer, capsys, monkeypatch):
    monkeypatch.setattr(harness, "Tracer", _RecordedTracer)
    # four seconds: the egress spans of a wave are drained with the next
    # wave's, so a window that holds one slow wave would read none
    rc, lines = _run(steer, capsys, _cells()[0], trace=1, seconds=4.0)
    last = lines[-1]
    assert rc == 0 and last["correct"] is True
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert 0 < m[ENQUEUE] < m["egress.result_ms_per_wave"]
