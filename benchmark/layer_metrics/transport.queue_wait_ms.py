"""Mean time a durable-queue message (a sign request) waited between
``enqueue`` and the start of its handler on a queue worker: the
program's ``transport.queue_wait_s`` over the window, all nodes."""

from benchmark import span_reduce


def read(run):
    return span_reduce.histogram_mean_ms(run, "transport.queue_wait_s")
