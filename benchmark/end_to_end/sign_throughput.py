"""Verified signatures completed in the window over the time from the
window's start to the last counted completion."""

from benchmark import stats


def read(run):
    return stats.throughput([r.done_ns for r in run.measured], run.good,
                            run.window_start_ns)
