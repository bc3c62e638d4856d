"""Sum of the ``phase:gg18_*`` span durations of a wave: the device work of
the ten GG18 round handlers of a signer's batch (each span ends in
``block_until_ready`` while tracing is armed), mean over the nodes and the
measured waves. Host clock, from the program's flight recorder; the names
are the program's (``protocol/ecdsa/batch_signing.py`` ``PHASE_SPANS``, by
way of the scheme file)."""

from benchmark import span_reduce


def read(run):
    names = set(getattr(run.scheme, "PHASE_SPANS", ()))
    spans = span_reduce.window_spans(run, lambda n: n in names)
    return span_reduce.ms_per_node_and_wave(
        run, sum(span_reduce.duration_ms(s) for s in spans), spans)
