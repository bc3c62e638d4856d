"""CPU a node's batch thread (``bsign-<batch>``: claims, share loads, the
party's construction, the session's start) spent a wave, in ms: the rise
of ``interp.cpu_s.bsign`` over the window, shared among the signing nodes
(``run.quorum``) and the measured waves. Beside ``batch.prepare_ms_per_wave``
(the same thread's wall time) it says how much of prepare ran and how much
waited."""

from benchmark import interp_reduce


def read(run):
    cpu_s = interp_reduce.cpu_delta_s(run, ["bsign"])
    shares = run.quorum * len(run.measured_waves)
    if cpu_s is None or not shares:
        return None
    return cpu_s / shares * 1e3
