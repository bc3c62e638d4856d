"""Per request, from the client's ``sign_transaction`` call to its result
event; the median over all requests of the window (nearest rank)."""

from benchmark import stats


def read(run):
    return stats.percentile(run.latencies_ms(), 50)
