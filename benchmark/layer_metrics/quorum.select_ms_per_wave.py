"""Choosing who signs a batch (the wallet's participants that this node's
registry lists READY, at least t + 1 of them, the smallest the leader): the
``host:quorum_select`` spans, a node and wave. A program without the span
(before ``LocalCluster.stop_node``) gives None."""

from benchmark import span_reduce


def read(run):
    return span_reduce.stage_ms_per_wave(run, "host:quorum_select")
