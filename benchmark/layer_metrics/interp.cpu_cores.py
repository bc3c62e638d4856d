"""How many cores' worth of CPU the whole process used over the window:
the rise of every ``interp.cpu_s.<role>`` gauge (the client's thread, the
fabric's workers, the batch and sender threads, the profiler's start and
stop on the caller's thread in a traced run) over the window's seconds.
About 1.0 says the one interpreter is the wave (above 1 only by what
runs with it released: OpenSSL, ``pread``, XLA's dispatch); well under 1
says the threads sleep on something else."""

from benchmark import interp_reduce


def read(run):
    cpu_s = interp_reduce.cpu_delta_s(run)
    if cpu_s is None or run.window_ns <= 0:
        return None
    return cpu_s / (run.window_ns / 1e9)
