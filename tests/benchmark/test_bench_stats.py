"""The end-to-end arithmetic on hand-made completions (no JAX)."""
import pytest

from benchmark import stats


@pytest.mark.parametrize("values,q,want", [
    ([5, 1, 3, 2, 4], 50, 3),
    ([5, 1, 3, 2, 4], 95, 5),
    ([1, 2, 3, 4], 50, 2),           # nearest rank, not interpolation
    (list(range(1, 101)), 95, 95),
    (list(range(1, 101)), 100, 100),
    ([7], 95, 7),
])
def test_percentile_is_nearest_rank(values, q, want):
    assert stats.percentile(values, q) == want


@pytest.mark.parametrize("values,q", [([], 50), ([1], 0), ([1], 101)])
def test_percentile_refuses_nonsense(values, q):
    with pytest.raises(ValueError):
        stats.percentile(values, q)


def test_throughput_runs_to_the_last_counted_completion():
    start = 1_000_000_000
    done = [start + 2_000_000_000, start + 4_000_000_000, None,
            start + 9_000_000_000]
    ok = [True, True, False, False]  # the 9 s one failed: not counted
    assert stats.throughput(done, ok, start) == pytest.approx(2 / 4.0)


def test_throughput_of_nothing_is_zero_and_time_cannot_run_backwards():
    assert stats.throughput([None], [False], 5) == 0.0
    with pytest.raises(ValueError):
        stats.throughput([4], [True], 5)


def test_a_failed_request_counts_as_the_whole_window():
    lat = stats.latencies_ms([0, 0, 0], [2_000_000, 3_000_000, None],
                             [True, False, False], window_ns=50_000_000)
    assert lat == [2.0, 50.0, 50.0]


def test_growth_is_last_over_first_minus_one():
    assert stats.growth_pct([10.0, 12.0, 15.0]) == pytest.approx(50.0)
    assert stats.growth_pct([10.0]) is None
