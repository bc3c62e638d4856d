"""Least fill of any manifest the schedulers fired (the program's
``scheduler.batch_fill_ratio`` histogram, min over the nodes)."""


def read(run):
    fills = [s["histograms"].get("scheduler.batch_fill_ratio", {})
             for s in run.metrics_end.values()]
    mins = [f["min"] for f in fills if f.get("count")]
    return min(mins) if mins else None
