"""Most sign requests any node's signing bridge held at once (each holds
a queue worker until its reply): the program's ``bridge.inflight_peak``
gauge at the window's end, max over the nodes."""


def read(run):
    peaks = [s["gauges"]["bridge.inflight_peak"]
             for s in run.metrics_end.values()
             if "bridge.inflight_peak" in s["gauges"]]
    return max(peaks) if peaks else None
