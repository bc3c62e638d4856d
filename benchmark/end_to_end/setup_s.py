"""Everything from the process's start to the window's start: imports,
cluster, wallets and sealed share writes, the warm compile (or the cache's
loads), the unmeasured wave. The reference's check is after the window
and not in it."""


def read(run):
    return run.setup_s
