"""Of all programs' device time in the traced wave, the share of the
programs of wire rounds 2 and 3: the MtA responses with their proofs, and
the verification and decryption of the peers' (the scheme file's
``MTA_KERNELS``, as ``jit_<name>``)."""


def read(run):
    if run.trace is None or not run.traced_waves:
        return None
    names = {"jit_" + k for k in getattr(run.scheme, "MTA_KERNELS", ())}
    seconds = run.program_seconds()
    total = sum(seconds.values())
    if not names or total <= 0:
        return None
    return sum(v for k, v in seconds.items() if k in names) / total * 100.0
