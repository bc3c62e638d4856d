"""Result egress after the last round (a result event, canonical JSON,
the idempotent enqueue, the reply inbox and the claim, per request): the
``host:result_egress`` spans, a node and wave."""

from benchmark import span_reduce


def read(run):
    return span_reduce.stage_ms_per_wave(run, "host:result_egress")
