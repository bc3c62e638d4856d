"""What a thread that lets go of the interpreter waits to get it back, in
ms: the mean by which the program's ``interp-canary`` thread overslept its
fixed 10 ms (100 wake-ups a second, one interpreter acquisition each),
the histogram ``interp.handover_lag_s`` over the window."""

from benchmark import span_reduce


def read(run):
    return span_reduce.histogram_mean_ms(run, "interp.handover_lag_s")
