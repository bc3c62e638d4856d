"""The stage readers PR 25 adds under benchmark/layer_metrics/, each fed a
hand-made ``RunData`` (spans, registry snapshots, the recorded
benchmark/data/trace_small.json) and checked against a number worked out
by hand; ``None`` where there is nothing to read, as on a program that
lacks the span or the counter. No JAX, no chip. Also here: the five
signing kernels' function names are the names the trace reduction looks
for."""
import copy
import json
import os
import re
from types import SimpleNamespace

import pytest

from benchmark import harness, opcounts, span_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "ed25519-2of3.bulk-waves"
MS = 1_000_000
WINDOW = 1_000 * MS  # the measured window starts here (monotonic ns)
OFFSET = 102_737_800_853  # trace_small.json: monotonic_ns - profiler_ns
LO, HI = 5_500_000_000, 6_000_000_000  # profiler clock, around its programs
# the device of trace_small.json is idle in [LO, HI) before its first
# program, for ~5 us between programs, and after the last one
FIRST_GAP = (LO, 5_528_951_368)
LAST_GAP = (5_950_771_173, HI)
IDLE_NS = 500_000_000 - 421_804_217

NEW = ["client.enqueue_ms_per_sign", "transport.queue_wait_ms",
       "bridge.inflight_peak", "intake.handle_ms_per_sign",
       "batch.admit_ms_per_wave", "batch.prepare_ms_per_wave",
       "session.wire_ms_per_wave", "egress.result_ms_per_wave",
       "host.unnamed_idle_pct", "trace.spans_dropped"]


def span(name, node, t0_ms, dur_ms, span_id=None, parent_id=None, **attrs):
    t0 = WINDOW + int(t0_ms * MS)
    return {"name": name, "node": node, "tid": "t", "trace_id": "x",
            "span_id": span_id or f"{name}@{node}@{t0_ms}",
            "parent_id": parent_id, "t0_ns": t0,
            "t1_ns": t0 + int(dur_ms * MS), "kind": "X", "attrs": attrs}


def hist(total, count):
    return {"sum": total, "count": count, "min": 0.0, "max": total}


def snapshot(histograms=None, gauges=None):
    return {"counters": {}, "gauges": gauges or {},
            "histograms": histograms or {}}


def make_run(spans, start, end, trace=None):
    """A RunData as harness.measure makes one: an unmeasured wave, two
    measured ones, two nodes' registries at the window's two ends."""
    waves = [SimpleNamespace(measured=m, requests=[])
             for m in (False, True, True)]
    served = SimpleNamespace(config={}, wave_size=4, quorum=2,
                             metrics_snapshot=lambda: end)
    run = harness.RunData(
        served, {"waves": waves, "window_start_ns": WINDOW,
                 "window_end_ns": WINDOW + 10_000 * MS}, start, spans)
    if trace is not None:
        run.trace, run.traced_waves = trace, 1
        run.traced_lo_ns, run.traced_hi_ns = LO, HI
    return run


@pytest.fixture(scope="module")
def programs_only():
    """trace_small.json as a traced run records it: programs, no
    operations."""
    with open(os.path.join(ROOT, "benchmark", "data",
                           "trace_small.json")) as fh:
        t = copy.deepcopy(json.load(fh))
    dev = t["planes"][0]
    dev["lines"] = [ln for ln in dev["lines"] if ln["name"] == "XLA Modules"]
    return t


def on_host_clock(gap, name, node="a"):
    """A span that covers exactly ``gap`` (profiler ns), on the host's
    clock."""
    return {"name": name, "node": node, "tid": "t", "trace_id": "x",
            "span_id": f"{name}@{gap[0]}", "parent_id": None,
            "t0_ns": gap[0] + OFFSET, "t1_ns": gap[1] + OFFSET,
            "kind": "X", "attrs": {}}


@pytest.fixture()
def run(programs_only):
    spans = [
        # before the window (the unmeasured wave): never read
        span("client:submit", "client", -500, 50, tx="w", sign_s=0.001),
        span("host:manifest_admit", "a", -400, 999),
        # client SDK: (5 - 2) and (4 - 3) ms beyond the signature
        span("client:submit", "client", 0, 5, tx="x", sign_s=0.002),
        span("client:submit", "client", 6, 4, tx="y", sign_s=0.003),
        # batch stages, two nodes, two waves
        span("host:manifest_admit", "a", 10, 10),
        span("host:manifest_admit", "a", 5000, 20),
        span("host:manifest_admit", "b", 10, 30),
        span("host:manifest_admit", "b", 5000, 40),
        span("host:batch_prepare", "a", 30, 100),
        span("host:batch_prepare", "a", 5030, 100),
        span("host:batch_prepare", "b", 30, 200),
        span("host:batch_prepare", "b", 5030, 200),
        span("host:result_egress", "a", 900, 8),
        span("host:result_egress", "a", 5900, 8),
        span("host:result_egress", "b", 900, 4),
        span("host:result_egress", "b", 5900, 4),
        # the wire stage. Node a: a round of 100 ms whose two phase
        # children cover 10..70 and 60..90 of it (80 ms together, so 20
        # of its own), and 5 ms of inbound envelope; a phase that is
        # another round's child takes nothing off. Node b: a round of
        # 50 ms with no children, 5 ms of envelope
        span("round:r1", "a", 300, 100, span_id="ra"),
        span("phase:bsign_x", "a", 310, 60, parent_id="ra"),
        span("phase:bsign_y", "a", 360, 30, parent_id="ra"),
        span("phase:bsign_z", "a", 320, 10, parent_id="elsewhere"),
        span("host:envelope_in", "a", 295, 5, round="r1", sender="b"),
        span("round:r1", "b", 300, 50, span_id="rb"),
        span("host:envelope_in", "b", 295, 5, round="r1", sender="a"),
        # waits: they name no work
        span("queue", "a", 0, 9000),
        span("session", "a", 200, 800),
        span("wait:hello", "a", 200, 90),
        # on the traced wave: a wait over its first idle gap, work over
        # its last
        on_host_clock(FIRST_GAP, "queue"),
        on_host_clock(LAST_GAP, "host:result_egress", node="traced"),
    ]
    start = {
        "a": snapshot({"transport.queue_wait_s": hist(1.0, 10),
                       "intake.handle_s": hist(0.1, 100)},
                      {"trace.dropped_spans": 2.0}),
        "b": snapshot(gauges={"trace.dropped_spans": 0.0}),
    }
    end = {
        "a": snapshot({"transport.queue_wait_s": hist(1.5, 20),
                       "intake.handle_s": hist(0.5, 300)},
                      {"trace.dropped_spans": 5.0,
                       "bridge.inflight_peak": 341.0}),
        "b": snapshot({"transport.queue_wait_s": hist(0.5, 30),
                       "intake.handle_s": hist(0.4, 200)},
                      {"trace.dropped_spans": 1.0,
                       "bridge.inflight_peak": 352.0}),
    }
    return make_run(spans, start, end, programs_only)


def reader(name):
    return harness.Cell(ROOT, CELL).reader("per_layer", name)


@pytest.mark.parametrize("name,by_hand", [
    ("client.enqueue_ms_per_sign", ((5 - 2) + (4 - 3)) / 2),
    # (1.5 - 1.0 + 0.5) s over (20 - 10 + 30) messages
    ("transport.queue_wait_ms", 1.0 / 40 * 1e3),
    ("bridge.inflight_peak", 352.0),
    # (0.5 - 0.1 + 0.4) s over (300 - 100 + 200) requests
    ("intake.handle_ms_per_sign", 0.8 / 400 * 1e3),
    ("batch.admit_ms_per_wave", (10 + 20 + 30 + 40) / 2 / 2),
    ("batch.prepare_ms_per_wave", (100 + 100 + 200 + 200) / 2 / 2),
    ("session.wire_ms_per_wave", ((100 - 80) + 5 + 50 + 5) / 2 / 2),
    # the span on the traced wave's last gap is a third node's
    ("egress.result_ms_per_wave",
     (8 + 8 + 4 + 4 + (LAST_GAP[1] - LAST_GAP[0]) / MS) / 3 / 2),
    # all idle time but the last gap, which a host: span covers
    ("host.unnamed_idle_pct",
     (1 - (LAST_GAP[1] - LAST_GAP[0]) / IDLE_NS) * 100),
    ("trace.spans_dropped", (5 + 1) - (2 + 0)),
])
def test_a_stage_reader_gives_the_number_worked_out_by_hand(
        run, name, by_hand):
    assert reader(name)(run) == pytest.approx(by_hand, rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_a_stage_reader_finds_nothing_on_a_program_without_its_source(
        name, programs_only):
    """An older program: no stage span, no new histogram or gauge, only
    what PR 24's tree recorded. Nothing raises; the metric is left out."""
    old = [span("queue", "a", 0, 9000), span("session", "a", 200, 800),
           span("intake", "a", 1, 0)]
    old[-1]["kind"] = "i"
    plain = {"a": snapshot({"scheduler.dispatch_age_s": hist(1.0, 4)})}
    run = make_run(old, plain, plain,
                   programs_only if name == "host.unnamed_idle_pct" else None)
    got = reader(name)(run)
    if name == "host.unnamed_idle_pct":
        # a trace there is: every idle nanosecond is unnamed (the
        # instant is a work span of no length)
        assert got == pytest.approx(100.0)
    else:
        assert got is None
    assert reader(name)(make_run([], {}, {})) is None


def test_a_gap_under_a_queue_span_is_unnamed_and_under_a_host_span_not(
        programs_only):
    waits = [on_host_clock((LO, HI), "queue"),
             on_host_clock((LO, HI), "bench:await_results"),
             on_host_clock((LO, HI), "session"),
             on_host_clock((LO, HI), "wait:hello")]
    read = reader("host.unnamed_idle_pct")
    assert read(make_run(waits, {}, {}, programs_only)) == pytest.approx(100)
    first = on_host_clock(FIRST_GAP, "host:manifest_admit")
    assert read(make_run(waits + [first], {}, {}, programs_only)) == (
        pytest.approx((1 - (FIRST_GAP[1] - FIRST_GAP[0]) / IDLE_NS) * 100))
    for work in ("client:submit", "intake", "dispatch", "round:r1",
                 "phase:bsign_x", "host:batch_prepare"):
        whole = on_host_clock((LO, HI), work)
        assert read(make_run(waits + [whole], {}, {}, programs_only)) == (
            pytest.approx(0.0, abs=1e-9)), work


def test_the_manifest_lists_the_stage_metrics_for_the_accepted_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    cells = [w["name"] for w in manifest["workloads"]][:3]
    for name in NEW:
        assert entries[name]["workloads"] == cells, name
    assert span_reduce.WORK_PREFIXES == (
        "client:", "intake", "dispatch", "host:", "round:", "phase:")


def test_the_signing_kernels_are_named_as_the_trace_reduction_expects():
    """``kernels.device_ms_per_wave`` and ``kernels.achieved_gops`` find
    the kernels' programs as ``jit_<function name>``: the jitted engine
    functions the served party calls are exactly ``opcounts.KERNELS``."""
    from mpcium_tpu.engine import eddsa_batch as eb

    with open(os.path.join(ROOT, "mpcium_tpu", "protocol", "eddsa",
                           "batch_signing.py")) as fh:
        called = set(re.findall(r"\beb\.(\w+)\(", fh.read()))
    jitted = {n for n in called if hasattr(getattr(eb, n), "lower")}
    assert jitted == set(opcounts.KERNELS)
    for name in opcounts.KERNELS:
        assert getattr(eb, name).__name__ == name
