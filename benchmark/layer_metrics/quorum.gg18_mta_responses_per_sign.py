"""MtA responses a served signature took: the rise of the program's
``party.ecdsa.mta_responses_total`` over the window (a signer counts, a
batch, 2 legs (gamma and w) x its peers x the batch's lanes, when its
round-2 handler has made them), all nodes, over the window's requests. q
signers answer q - 1 peers each: 2 q (q - 1) a signature, 12 with every
node of three signing and 4 with one out, or the quorum was not the one
the configuration states. A stopped node's counter stands still. None on a
program without the counter."""

from benchmark import interp_reduce


def read(run):
    made = interp_reduce.counter_delta(run, "party.ecdsa.mta_responses_total")
    if made is None or not run.measured:
        return None
    return made / len(run.measured)
