"""CPU the caller's thread spent inside one ``sign_transaction`` call, in
ms: mean ``cpu_s`` of the window's ``client:submit`` spans. Read as a mean
over the window's thousands of spans only (one span is shorter than the
thread clock's step). Beside it stand the span's own wall time (``sign_s``
+ ``client.enqueue_ms_per_sign``) and the loop's (``client.submit_ms_per_sign``):
wall less CPU is what the caller's thread waited, for the interpreter or
a lock."""

from benchmark import interp_reduce


def read(run):
    spans = interp_reduce.cpu_spans(run, ["client:submit"])
    if not spans:
        return None
    return sum(s["attrs"]["cpu_s"] for s in spans) / len(spans) * 1e3
