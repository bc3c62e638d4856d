"""The session's host wire stage: each ``round:*`` span's self time (its
duration less what its ``phase:*`` children cover: packing, signing and
routing envelopes, the party's host arithmetic) plus the
``host:envelope_in`` spans (decode and verify of an inbound envelope), a
node and wave."""

from benchmark import span_reduce


def read(run):
    rounds = span_reduce.window_spans(run, lambda n: n.startswith("round:"))
    if not rounds:
        return None
    phases = {}
    for s in span_reduce.window_spans(run, lambda n: n.startswith("phase:")):
        phases.setdefault(s.get("parent_id"), []).append(s)
    inbound = span_reduce.window_spans(run, lambda n: n == "host:envelope_in")
    total_ms = (
        sum(span_reduce.self_ms(r, phases.get(r["span_id"], []))
            for r in rounds)
        + sum(span_reduce.duration_ms(s) for s in inbound))
    return span_reduce.ms_per_node_and_wave(run, total_ms, rounds)
