"""Manifest admission (parse, leader signature, every initiator signature
again, claims): the ``host:manifest_admit`` spans, a node and wave."""

from benchmark import span_reduce


def read(run):
    return span_reduce.stage_ms_per_wave(run, "host:manifest_admit")
