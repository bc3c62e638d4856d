"""The five readers of the cell ``secp-2of3-paillier.gg18-waves``
(benchmark/layer_metrics/gg18.*.py), each fed a hand-made ``RunData``
(recorded spans; the small recorded trace with its programs renamed to
GG18 round programs) and checked against a number worked out by hand;
``None`` where there is nothing to read. No JAX program, no chip."""
import copy
import json
import os
from types import SimpleNamespace

import pytest

from benchmark import harness, peaks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "secp-2of3-paillier.gg18-waves"
MS = 1_000_000
WINDOW = 1_000 * MS
LO, HI = 5_500_000_000, 6_000_000_000  # profiler clock: trace_small.json
NEW = ["gg18.phase_ms_per_wave", "gg18.wire_ms_per_wave",
       "gg18.mta_device_share_pct", "gg18.achieved_gops",
       "gg18.mxu_roofline_pct"]
# trace_small.json's four program runs (ns), renamed below
RUNS = [103647605, 103648197, 103648283, 110860132]


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(ROOT, CELL)


def span(name, node, t0_ms, dur_ms, span_id=None, parent_id=None, **attrs):
    t0 = WINDOW + int(t0_ms * MS)
    return {"name": name, "node": node, "tid": "t", "trace_id": "x",
            "span_id": span_id or f"{name}@{node}@{t0_ms}",
            "parent_id": parent_id, "t0_ns": t0,
            "t1_ns": t0 + int(dur_ms * MS), "kind": "X", "attrs": attrs}


def make_run(cell, spans, trace=None, wave=32, quorum=3):
    waves = [SimpleNamespace(measured=m, requests=[])
             for m in (False, True, True)]
    served = SimpleNamespace(config={}, wave_size=wave, quorum=quorum,
                             scheme=cell.scheme, metrics_snapshot=dict)
    run = harness.RunData(
        served, {"waves": waves, "window_start_ns": WINDOW,
                 "window_end_ns": WINDOW + 10_000 * MS}, {}, spans)
    if trace is not None:
        run.trace, run.traced_waves = trace, 1
        run.traced_lo_ns, run.traced_hi_ns = LO, HI
    return run


@pytest.fixture(scope="module")
def gg18_trace():
    """trace_small.json as a traced run records it (programs, no
    operations), its four program runs renamed: a round-2 program, a
    round-3 program, a curve program, and one that is no GG18 program."""
    with open(os.path.join(ROOT, "benchmark", "data",
                           "trace_small.json")) as fh:
        t = copy.deepcopy(json.load(fh))
    dev = t["planes"][0]
    dev["lines"] = [ln for ln in dev["lines"] if ln["name"] == "XLA Modules"]
    names = ["jit_gg18_r2_respond(1)", "jit_gg18_r3_verify(2)",
             "jit_gg18_r5a_commit(3)", "jit_something_else(4)"]
    for ev, name in zip(dev["lines"][0]["events"], names):
        ev[0] = name
    return t


@pytest.fixture()
def run(cell, gg18_trace):
    P = cell.scheme.PHASE_SPANS
    spans = [
        # before the window (the unmeasured wave): never read
        span(P[0], "a", -500, 999, parent_id="old"),
        # node a, wave 1: the start handler's round of 100 ms with a phase
        # over 10..70 of it; a round-2 message's round of 50 ms whose phase
        # covers 5..45; a round that completed no stage: 2 ms, no phase
        span("round:start", "a", 0, 100, span_id="a0"),
        span(P[0], "a", 10, 60, parent_id="a0"),
        span("round:gg18/b/2/respond", "a", 200, 50, span_id="a2"),
        span(P[2], "a", 205, 40, parent_id="a2"),
        span("round:gg18/b/2/respond", "a", 190, 2, span_id="a2x"),
        span("host:envelope_in", "a", 180, 3, round="gg18/b/2/respond"),
        # node b, wave 2: one round of 30 ms, its phase 20 of them
        span("round:gg18/b/9/partial", "b", 5000, 30, span_id="b9"),
        span(P[9], "b", 5005, 20, parent_id="b9"),
        span("host:envelope_in", "b", 4990, 1, round="gg18/b/9/partial"),
        # an Ed25519 round and phase: another scheme's, not read
        span("round:r1", "a", 300, 1000, span_id="ed"),
        span("phase:bsign_x", "a", 310, 500, parent_id="ed"),
        # waits name no work
        span("session", "a", 0, 9000),
    ]
    return make_run(cell, spans, gg18_trace)


def reader(cell, name):
    return cell.reader("per_layer", name)


def test_the_cell_reports_the_sixteen_shared_entries_and_its_five(cell):
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert names[-5:] == NEW and len(names) == 16 + 5
    for m in cell.metrics("per_layer")[-5:]:
        assert m["workloads"] == [CELL] and m["moves"] == "sign_throughput"
    # and the three Ed25519-only entries are not the cell's
    assert not {"party.phase_ms_per_wave", "session.wire_ms_per_wave",
                "kernels.achieved_gops"} & set(names)
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "sign_throughput", "sign_latency_p50_ms", "sign_latency_p95_ms",
        "setup_s"]


def test_the_phase_and_wire_readers_give_the_numbers_by_hand(cell, run):
    # phases: 60 + 40 + 20 ms over two nodes and two waves
    assert reader(cell, NEW[0])(run) == pytest.approx((60 + 40 + 20) / 2 / 2)
    # wire: (100 - 60) + (50 - 40) + 2 + (30 - 20) of rounds' own time and
    # 3 + 1 of envelopes, over the same
    assert reader(cell, NEW[1])(run) == pytest.approx(
        (40 + 10 + 2 + 10 + 3 + 1) / 2 / 2)


def test_the_trace_readers_give_the_numbers_by_hand(cell, run):
    total = sum(RUNS)
    assert reader(cell, NEW[2])(run) == pytest.approx(
        (RUNS[0] + RUNS[1]) / total * 100)
    ops = sum(cell.scheme.ops_per_wave(32, 3).values())
    gg18_s = (RUNS[0] + RUNS[1] + RUNS[2]) / 1e9
    assert reader(cell, NEW[3])(run) == pytest.approx(ops / gg18_s / 1e9)


def test_the_mxu_share_is_of_the_published_peak(cell, run, monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [
        SimpleNamespace(device_kind="TPU v5 lite")])
    mxu = sum(cell.scheme.mxu_ops_per_wave(32, 3).values())
    mod_s = (RUNS[0] + RUNS[1]) / 1e9  # the programs with modular products
    peak = peaks.for_device("TPU v5 lite")["bf16_flops_per_s"]
    assert peak == 197e12
    assert reader(cell, NEW[4])(run) == pytest.approx(
        2 * mxu / mod_s / peak * 100)
    # a device with no published peak is an error, not a default
    monkeypatch.setattr(jax, "devices", lambda *a: [
        SimpleNamespace(device_kind="cpu")])
    with pytest.raises(KeyError):
        reader(cell, NEW[4])(run)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_where_there_is_nothing(cell, name):
    """No span of the GG18 party, no trace: nothing raises, the metric is
    left out of the line."""
    other = [span("round:r1", "a", 300, 100, span_id="ed"),
             span("phase:bsign_x", "a", 310, 50, parent_id="ed"),
             span("round:gg18/b/2/respond", "a", 0, 5)]
    assert reader(cell, name)(make_run(cell, other)) is None
    assert reader(cell, name)(make_run(cell, [])) is None


def test_rounds_without_a_phase_span_give_no_wire_stage(cell, gg18_trace):
    """A program whose GG18 party opens no ``phase:`` span: a round's self
    time would hold its device work, so the reader gives nothing."""
    rounds = [span("round:gg18/b/2/respond", "a", 0, 5000)]
    assert reader(cell, NEW[1])(make_run(cell, rounds, gg18_trace)) is None
    assert reader(cell, NEW[0])(make_run(cell, rounds, gg18_trace)) is None


def test_a_trace_without_gg18_programs_reads_zero_share_and_no_rate(
        cell):
    with open(os.path.join(ROOT, "benchmark", "data",
                           "trace_small.json")) as fh:
        t = json.load(fh)
    run = make_run(cell, [], t)
    assert reader(cell, NEW[2])(run) == 0.0
    assert reader(cell, NEW[3])(run) is None
    assert reader(cell, NEW[4])(run) is None
