"""Payload bytes (``tx``) a request carried into a node: the program's
``intake.tx_bytes_total`` over the requests it took in (the count of
``intake.handle_s``), end of the window less its start, all nodes. A mix
of Solana messages reads its mean length (~233); 32-byte digests read 32.
A program without the counter (before PR 43) gives None."""

from benchmark import interp_reduce


def read(run):
    sent = interp_reduce.counter_delta(run, "intake.tx_bytes_total")
    taken = sum(
        snap["histograms"].get("intake.handle_s", {}).get("count", 0)
        - run.metrics_start.get(nid, {}).get("histograms", {})
        .get("intake.handle_s", {}).get("count", 0)
        for nid, snap in run.metrics_end.items())
    if sent is None or not taken:
        return None
    return sent / taken
