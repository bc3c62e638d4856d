"""Last measured wave's seconds over the first's, minus one: how much the
cluster's host objects slow identical work down within one window."""

from benchmark import stats


def read(run):
    return stats.growth_pct([w.seconds for w in run.measured_waves])
