"""1 - (union of the intervals in which an operation ran on the device)
over the traced span, mean over the cell's devices. Profiler trace."""


def read(run):
    if run.trace is None:
        return None
    return run.busy_and_idle()["idle_share_pct"]
