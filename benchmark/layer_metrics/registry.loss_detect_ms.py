"""Mean time a live node's registry took to drop a peer that left, in ms:
from the last heartbeat it saw the peer's ready key change to the poll
that found the peer gone, the program's ``registry.loss_detect_s`` at the
window's start (the departures are part of set-up), sum over count, all
nodes. None where no peer was lost, and on a program whose registry keeps
no such histogram."""

NAME = "registry.loss_detect_s"


def read(run):
    total = count = 0.0
    for snap in run.metrics_start.values():
        h = snap.get("histograms", {}).get(NAME, {})
        total += h.get("sum") or 0.0
        count += h.get("count") or 0
    return total / count * 1e3 if count else None
