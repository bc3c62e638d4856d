"""``gg18.phase_ms_per_wave`` of the batches that ran DEGRADED: the sum of
the ``phase:gg18_*`` span durations (the scheme file's ``PHASE_SPANS``)
whose ``q`` attribute (the party's signers) is below the committee's size
(``scheme.n_nodes``), mean over the nodes that wrote them and the measured
waves. None where no batch ran degraded, and on a program whose GG18 phase
spans carry no ``q``."""

from benchmark import span_reduce


def read(run):
    committee = run.config["scheme"]["n_nodes"]
    names = set(getattr(run.scheme, "PHASE_SPANS", ()))
    spans = [s for s in span_reduce.window_spans(run, lambda n: n in names)
             if (s.get("attrs") or {}).get("q", committee) < committee]
    return span_reduce.ms_per_node_and_wave(
        run, sum(span_reduce.duration_ms(s) for s in spans), spans)
