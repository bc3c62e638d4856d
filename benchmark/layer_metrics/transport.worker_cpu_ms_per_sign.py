"""CPU the loopback fabric's two worker pools spent a served signature, in
ms: the rise of ``interp.cpu_s.loopback`` (pub/sub: ``_on_sign``, manifests,
envelopes, and whatever handler a delivery runs, the party's rounds and
result egress among them) and ``interp.cpu_s.loopback-q`` (the durable
queue: the signing bridges, result deliveries) over the window, shared
among the window's requests."""

from benchmark import interp_reduce


def read(run):
    return interp_reduce.per_sign_ms(
        run, interp_reduce.cpu_delta_s(run, ["loopback", "loopback-q"]))
