"""The GG18 session's host wire stage: the self time of each round span of
a batch (``round:gg18/b/<n>/...``, and ``round:start``, whose handler sends
round 1), that is its duration less what its ``phase:gg18_*`` children
cover (parsing and hex of the blocks, signing and routing nine rounds of
envelopes), plus the ``host:envelope_in`` spans (decode and verify of an
inbound envelope), a node and wave. A program whose GG18 party opens no
``phase:`` span gives nothing: a round's self time would then hold its
device work too."""

from benchmark import span_reduce


def read(run):
    names = set(getattr(run.scheme, "PHASE_SPANS", ()))
    phases = {}
    for s in span_reduce.window_spans(run, lambda n: n in names):
        phases.setdefault(s.get("parent_id"), []).append(s)
    rounds = span_reduce.window_spans(
        run, lambda n: n.startswith("round:gg18/b/") or n == "round:start")
    if not rounds or not phases:
        return None
    inbound = span_reduce.window_spans(run, lambda n: n == "host:envelope_in")
    total_ms = (
        sum(span_reduce.self_ms(r, phases.get(r["span_id"], []))
            for r in rounds)
        + sum(span_reduce.duration_ms(s) for s in inbound))
    return span_reduce.ms_per_node_and_wave(run, total_ms, rounds)
