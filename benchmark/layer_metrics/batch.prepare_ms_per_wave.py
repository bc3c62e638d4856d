"""Batch preparation (claims, quorum, one share load per request, the
party, the session's subscriptions): the ``host:batch_prepare`` spans, a
node and wave."""

from benchmark import span_reduce


def read(run):
    return span_reduce.stage_ms_per_wave(run, "host:batch_prepare")
