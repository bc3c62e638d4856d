"""Spans the flight recorders' rings dropped inside the window (a wrapped
ring silently shrinks every span-derived metric): the program's monotone
``trace.dropped_spans`` gauge, end less start, summed over the rings."""

NAME = "trace.dropped_spans"


def read(run):
    ends = [s["gauges"][NAME] for s in run.metrics_end.values()
            if NAME in s["gauges"]]
    if not ends:
        return None
    return sum(ends) - sum(s.get("gauges", {}).get(NAME, 0.0)
                           for s in run.metrics_start.values())
