"""Of the wall time of the batch-level host stages, the share their own
thread was on a CPU: sum of ``cpu_s`` over sum of duration, over the
window's ``host:manifest_admit``, ``host:batch_prepare`` and
``host:result_egress`` spans that carry ``cpu_s``. The rest is that thread
waiting: for the interpreter, a lock or a file. A span's ``cpu_s`` counts
up to its duration (the thread clock's step can overshoot a short span),
so the share is at most 100."""

from benchmark import interp_reduce, span_reduce

STAGES = ("host:manifest_admit", "host:batch_prepare", "host:result_egress")


def read(run):
    spans = interp_reduce.cpu_spans(run, STAGES)
    wall_ms = sum(span_reduce.duration_ms(s) for s in spans)
    if wall_ms <= 0:
        return None
    on_cpu_ms = sum(min(s["attrs"]["cpu_s"] * 1e3, span_reduce.duration_ms(s))
                    for s in spans)
    return on_cpu_ms / wall_ms * 100.0
