"""Mean time of one ``put`` into a node's sealed share store (the value
sealed and written, its name recorded), in microseconds: the program's
``store.put_s`` at the window's start, sum over count, all nodes. Set-up
wrote the population by then (a put a wallet and node, through
``Node.save_share``), and a sign writes none. A program whose store keeps
no such histogram (before ``STORE_FORMAT`` 2) gives None."""

NAME = "store.put_s"


def read(run):
    total = count = 0.0
    for snap in run.metrics_start.values():
        h = snap.get("histograms", {}).get(NAME, {})
        total += h.get("sum") or 0.0
        count += h.get("count") or 0
    return total / count * 1e6 if count else None
