"""What the program's log lines cost their threads a served signature, in
ms: the rise of the counter ``log.emit_s_total`` (seconds from a line's
hand-over to the logger to the end of its ``write``: the handler's lock
waited for, the formatting, the write) over the window, shared among the
window's requests. ``log.lines_total`` beside it (PERF.md gives lines a
sign) is no metric of its own."""

from benchmark import interp_reduce


def read(run):
    return interp_reduce.per_sign_ms(
        run, interp_reduce.counter_delta(run, "log.emit_s_total"))
