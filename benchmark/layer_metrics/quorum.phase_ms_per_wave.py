"""``party.phase_ms_per_wave`` of the batches that ran DEGRADED: the sum of
the ``phase:bsign_*`` span durations whose ``q`` attribute (the party's
signers) is below the committee's size (``scheme.n_nodes``), mean over the
nodes that wrote them and the measured waves. None where no batch ran
degraded, and on a program whose phase spans carry no ``q``."""

from benchmark import span_reduce


def read(run):
    committee = run.config["scheme"]["n_nodes"]
    spans = [s for s in span_reduce.window_spans(
                 run, lambda n: n.startswith("phase:bsign_"))
             if (s.get("attrs") or {}).get("q", committee) < committee]
    return span_reduce.ms_per_node_and_wave(
        run, sum(span_reduce.duration_ms(s) for s in spans), spans)
