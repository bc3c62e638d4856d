"""Limb multiply-adds the five signing kernels need at the cell's shapes
(``benchmark/opcounts.py``) over the device time of those five kernels' programs
in the traced span, in 1e9 operations a second."""

from benchmark import opcounts


def read(run):
    if run.trace is None or not run.traced_waves:
        return None
    seconds = sum(run.kernel_program_seconds().values())
    if seconds <= 0:
        return None
    ops = sum(opcounts.per_wave(run.wave_size, run.quorum).values())
    return ops * run.traced_waves / seconds / 1e9
