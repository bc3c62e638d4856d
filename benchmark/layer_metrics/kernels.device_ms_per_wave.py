"""Device time of every program that ran per traced wave (the process
runs nothing on the device but the engine: its five kernels, SHA-512 and
the small conversions around them): the profiler trace's program events
(``XLA Modules``) that start inside the traced span, summed, averaged over
the cell's devices, divided by the waves traced."""


def read(run):
    if run.trace is None or not run.traced_waves:
        return None
    seconds = run.program_seconds()
    if not seconds:
        return None
    return sum(seconds.values()) * 1e3 / run.traced_waves
