"""What a ``sign_transaction`` call costs beyond the initiator signature:
the ``client:submit`` span's duration less its ``sign_s`` (canonical JSON
and the fabric's ``enqueue``), mean over the window's requests."""

from benchmark import span_reduce


def read(run):
    spans = span_reduce.window_spans(run, lambda n: n == "client:submit")
    if not spans:
        return None
    return sum(span_reduce.duration_ms(s)
               - s.get("attrs", {}).get("sign_s", 0.0) * 1e3
               for s in spans) / len(spans)
