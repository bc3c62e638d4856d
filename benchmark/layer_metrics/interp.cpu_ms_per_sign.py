"""CPU the whole process spent a served signature, in ms: the rise of
every ``interp.cpu_s.<role>`` gauge over the window, shared among the
window's requests."""

from benchmark import interp_reduce


def read(run):
    return interp_reduce.per_sign_ms(run, interp_reduce.cpu_delta_s(run))
