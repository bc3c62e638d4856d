"""A node's handling of one ``mpc:sign`` copy, entry to buffered (two JSON
parses, the initiator signature, the dedup claim, the scheduler's
``submit``): the program's ``intake.handle_s`` over the window."""

from benchmark import span_reduce


def read(run):
    return span_reduce.histogram_mean_ms(run, "intake.handle_s")
