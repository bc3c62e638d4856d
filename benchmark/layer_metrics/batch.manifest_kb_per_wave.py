"""Size of a sign manifest as a node received it (every request of the
batch rides in it, payload and initiator signature in hex): the ``bytes``
of the window's ``host:manifest_admit`` spans, a node and wave, in kB
(1,000 bytes). A program whose span lacks the attribute (before PR 43)
gives None."""

from benchmark import span_reduce


def read(run):
    spans = [s for s in span_reduce.window_spans(
        run, lambda n: n == "host:manifest_admit")
        if "bytes" in (s.get("attrs") or {})]
    total = span_reduce.ms_per_node_and_wave(
        run, float(sum(s["attrs"]["bytes"] for s in spans)), spans)
    return None if total is None else total / 1e3
