"""Limb multiply-adds the GG18 round programs need at the cell's shapes
(the scheme file's ``ops_per_wave``: ``schemes/secp256k1_opcounts.py``) over
the device time of those programs in the traced wave, in 1e9 operations a
second."""


def read(run):
    count = getattr(run.scheme, "ops_per_wave", None)
    if count is None or run.trace is None or not run.traced_waves:
        return None
    seconds = sum(run.kernel_program_seconds().values())
    if seconds <= 0:
        return None
    ops = sum(count(run.wave_size, run.quorum).values())
    return ops * run.traced_waves / seconds / 1e9
