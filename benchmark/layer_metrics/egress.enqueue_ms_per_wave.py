"""The part of result egress spent in the fabric's idempotent ``enqueue``
(q nodes each enqueue every result under the tx's key; q - 1 copies are
dropped by the duplicate window): the ``enqueue_s`` attribute of the
``host:result_egress`` spans, a node and wave."""

from benchmark import span_reduce


def read(run):
    spans = [s for s in span_reduce.window_spans(
        run, lambda n: n == "host:result_egress")
        if "enqueue_s" in (s.get("attrs") or {})]
    return span_reduce.ms_per_node_and_wave(
        run, sum(s["attrs"]["enqueue_s"] for s in spans) * 1e3, spans)
