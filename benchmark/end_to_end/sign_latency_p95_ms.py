"""Per request, from the client's ``sign_transaction`` call to its result
event; the 95th percentile over all requests of the window (nearest
rank); a failed request counts as the window's length."""

from benchmark import stats


def read(run):
    return stats.percentile(run.latencies_ms(), 95)
