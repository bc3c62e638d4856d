"""Mean time an entry sat buffered before its manifest fired (the
program's ``scheduler.dispatch_age_s``), over the window: the histogram's
sum and count at the window's end less those at its start, all nodes."""

NAME = "scheduler.dispatch_age_s"


def read(run):
    total = count = 0.0
    for nid, snap in run.metrics_end.items():
        end = snap["histograms"].get(NAME, {})
        start = run.metrics_start.get(nid, {}).get(
            "histograms", {}).get(NAME, {})
        total += (end.get("sum") or 0.0) - (start.get("sum") or 0.0)
        count += (end.get("count") or 0) - (start.get("count") or 0)
    return total / count * 1e3 if count else None
