"""Host clock around each measured wave's ``sign_transaction`` loop,
divided by the wave's size; mean over the measured waves."""


def read(run):
    waves = run.measured_waves
    if not waves:
        return None
    return sum(w.submit_seconds / w.size for w in waves) / len(waves) * 1e3
