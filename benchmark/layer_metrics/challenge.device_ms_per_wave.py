"""Device time of the RFC 8032 challenge hash per traced wave: the masked
SHA-512 program (``hash_bytes.PROGRAM``: every party's hash of R ‖ A ‖ M
over rows of different lengths, all blocks of the rung) among the profiler
trace's program events that start inside the traced span. A program
without that kernel (before PR 43) gives None."""

from benchmark import hash_bytes


def read(run):
    if run.trace is None or not run.traced_waves:
        return None
    seconds = run.program_seconds().get(hash_bytes.PROGRAM)
    if not seconds:
        return None
    return seconds * 1e3 / run.traced_waves
