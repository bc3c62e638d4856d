"""The reduction from a profiler trace to numbers, on the small recorded
trace kept with the benchmark (benchmark/data/trace_small.json, cut from a
real v5e trace) and on made-up intervals. No JAX, no chip."""
import copy
import json
import os

import pytest

from benchmark import trace_reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LO, HI = 5_500_000_000, 6_000_000_000  # a span around the four programs
OFFSET = 102_737_800_853  # monotonic_ns - profiler_ns, by hand (below)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(ROOT, "benchmark", "data",
                           "trace_small.json")) as fh:
        return json.load(fh)


@pytest.fixture()
def programs_only(recorded):
    """As a traced run records it: programs, no operations."""
    t = copy.deepcopy(recorded)
    dev = t["planes"][0]
    dev["lines"] = [ln for ln in dev["lines"] if ln["name"] == "XLA Modules"]
    return t


def test_clock_offset_is_the_median_of_the_annotations(recorded):
    # the six readings minus their starts, sorted, end in ...794643,
    # ...795424, ...800383, ...801324, ...802014, ...802073: the median
    # lies between the third and the fourth
    assert tr.clock_offset_ns(recorded) == OFFSET
    no_marks = {"planes": [p for p in recorded["planes"]
                           if p["name"].startswith("/device")]}
    with pytest.raises(ValueError):
        tr.clock_offset_ns(no_marks)


def test_program_seconds_sum_the_four_runs(programs_only):
    secs = tr.program_seconds(programs_only, LO, HI)
    assert secs == {"jit_nonce_commitments": pytest.approx(
        (103_647_605 + 103_648_197 + 103_648_283 + 110_860_132) / 1e9)}
    # only the runs that START in the span count
    assert tr.program_seconds(programs_only, 5_700_000_000, HI) == {
        "jit_nonce_commitments": pytest.approx(
            (103_648_283 + 110_860_132) / 1e9)}


def test_busy_union_and_idle_share_by_hand(programs_only):
    got = tr.busy_and_idle(programs_only, LO, HI)
    assert got["window_s"] == 0.5
    assert got["busy_s"] == pytest.approx(0.421804217)
    assert got["idle_share_pct"] == pytest.approx(
        (1 - 0.421804217 / 0.5) * 100)
    # clipped: a span that ends inside the second program
    half = tr.busy_and_idle(programs_only, LO, 5_700_000_000)
    assert half["busy_s"] == pytest.approx(
        (103_647_605 + (5_700_000_000 - 5_632_604_399)) / 1e9)


def test_operations_nest_and_the_union_counts_time_once(recorded):
    dev = tr.device_planes(recorded)[0]
    ops = tr.work_events(dev)
    assert len(ops) == 52  # operations win over programs where present
    brute = set()
    for _n, s, d in ops:
        brute.update(range(s, s + d))  # nanosecond by nanosecond
    assert tr.total_ns(tr.busy(dev, 0, 10**10)) == len(brute)


def test_gaps_are_named_by_the_host_span_that_covers_them(programs_only):
    host = [
        {"name": "bench:submit", "t0_ns": OFFSET + LO,
         "t1_ns": OFFSET + 5_520_000_000},
        {"name": "bench:await_results", "t0_ns": OFFSET + 5_520_000_000,
         "t1_ns": OFFSET + HI},
        {"name": "phase:bsign_nonce_commit", "t0_ns": OFFSET + 5_600_000_000,
         "t1_ns": OFFSET + 5_990_000_000},
    ]
    got = dict(tr.idle_gaps(programs_only, LO, HI, host))
    # before the first program: 28,951,368 ns, 20 ms of it under submit
    assert got["bench:submit"] == pytest.approx(0.028951368)
    # between the programs (5,426 + 5,107 + 5,055 ns): wholly inside both
    # await_results and the phase span -> the inner one names them
    assert got["phase:bsign_nonce_commit"] == pytest.approx(15_588 / 1e9)
    # after the last program: 49,228,827 ns; the phase span covers 39 ms
    # of it, await_results all of it
    assert got["bench:await_results"] == pytest.approx(0.049228827)
    assert sum(got.values()) == pytest.approx(0.5 - 0.421804217)
    assert dict(tr.idle_gaps(programs_only, LO, HI, [])) == {
        "unattributed": pytest.approx(0.5 - 0.421804217)}


def test_top_operations_drop_fingerprints_and_shells():
    t = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["%while.3 = (s32[]) while(...)", 0, 100],
            ["%fusion.1 = s32[8] fusion(...)", 0, 60],
            ["%fusion.1 = s32[8] fusion(...)", 60, 30],
            ["%copy.2", 90, 10]]}]}]}
    assert tr.top_device_ops(t, 0, 100) == [
        ["%fusion.1", pytest.approx(90e-9)], ["%copy.2", pytest.approx(10e-9)]]
    p = {"planes": [{"name": "/device:TPU:1", "lines": [
        {"name": "XLA Modules", "events": [["jit_f(123)", 5, 7]]}]}]}
    assert tr.top_device_ops(p, 0, 100) == [["jit_f", pytest.approx(7e-9)]]


def test_four_devices_average_and_no_device_is_an_error():
    def plane(i, events):
        return {"name": f"/device:TPU:{i}", "lines": [
            {"name": "XLA Modules", "events": events}]}

    t = {"planes": [plane(0, [["jit_k(1)", 0, 40]]),
                    plane(1, [["jit_k(1)", 0, 20]]),
                    plane(2, [["jit_k(1)", 10, 20]]),
                    plane(3, [["jit_k(1)", 60, 40]])]}
    got = tr.busy_and_idle(t, 0, 100)
    assert got["busy_s_per_device"] == [40e-9, 20e-9, 20e-9, 40e-9]
    assert got["idle_share_pct"] == pytest.approx(70.0)
    assert tr.program_seconds(t, 0, 100) == {"jit_k": pytest.approx(30e-9)}
    # a gap is where NO device runs: [40, 60)
    assert tr.idle_gaps(
        {"planes": t["planes"] + [{"name": "/host:CPU", "lines": [
            {"name": "python", "events": [["bench_clock:1000", 0, 1]]}]}]},
        0, 100, [{"name": "s", "t0_ns": 1000, "t1_ns": 1100}]) == [
            ["s", pytest.approx(20e-9)]]
    with pytest.raises(ValueError):
        tr.busy_and_idle({"planes": []}, 0, 100)


@pytest.mark.parametrize("intervals,want", [
    ([(5, 9), (1, 3), (2, 4), (9, 9)], [(1, 4), (5, 9)]),
    ([(1, 10), (2, 3)], [(1, 10)]),
    ([], []),
])
def test_union(intervals, want):
    assert tr.union(intervals) == want


def test_gaps_and_clip():
    assert tr.gaps([(2, 4), (6, 7)], 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert tr.gaps([], 3, 5) == [(3, 5)]
    assert tr.clip([(0, 5), (8, 12), (20, 30)], 4, 10) == [(4, 5), (8, 10)]
