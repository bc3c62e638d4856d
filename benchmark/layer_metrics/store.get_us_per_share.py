"""Mean time of one ``get`` from a node's sealed share store (the file
read and its seal opened; parsing the share is the caller's), in
microseconds: the program's ``store.get_s`` over the window, all nodes.
A program whose store keeps no such histogram gives None."""

from benchmark import span_reduce


def read(run):
    ms = span_reduce.histogram_mean_ms(run, "store.get_s")
    return None if ms is None else ms * 1e3
