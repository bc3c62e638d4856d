"""Of the traced wave's device-idle time (the complement of the union of
all devices' busy intervals), the share during which no work span of the
program (``client:``, ``intake``, ``dispatch``, ``host:``, ``round:``,
``phase:``) was open on any thread; ``queue``, ``session``, ``wait:`` and
``bench:`` spans are waits and name nothing."""

from benchmark import span_reduce


def read(run):
    return span_reduce.unnamed_idle_pct(run)
