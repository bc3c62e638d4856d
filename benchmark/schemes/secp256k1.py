"""The secp256k1 scheme: GG18 threshold ECDSA with Paillier MtA, as the
harness asks for it. Every configuration whose ``scheme.key_type`` is
``secp256k1`` is measured through this file (README.md says what a scheme
file holds). What is here: wallet keys by OpenSSL from the seed, never by
the program; Shamir shares mod n with their Feldman commitments; the
complete ``KeygenShare`` records with the GG18 ``aux`` of every share, from
the pre-parameters the cluster was built with; the plain reference, which
is OpenSSL's ECDSA verification of ``r || s`` over the 32-byte digest; the
low-s rule and the count of waves as rows of the check; the batched party
of the warm batch; the operation counts of its round programs
(``secp256k1_opcounts.py``, beside this file).

It imports, when it is loaded, the program's table of GG18 phase spans and
of round programs, which the readers of the ``gg18.*`` metrics take from
here: on a program that has neither (an older commit) a cell of this
scheme fails when it is loaded, in seconds, and not after a cold GG18
compile and two timed-out waves.
"""
from __future__ import annotations

import importlib.util
import os
import random
from typing import Dict, List, Sequence, Tuple

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives import hashes, serialization
from cryptography.hazmat.primitives.asymmetric import ec, utils

from mpcium_tpu.engine.gg18_batch import ROUND_PROGRAMS  # noqa: F401
from mpcium_tpu.protocol.ecdsa.batch_signing import PHASE_SPANS  # noqa: F401


def _beside(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location("bench_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


opcounts = _beside("secp256k1_opcounts")

KEY_TYPE = "secp256k1"

# the compile ledger's engine of the batched party: the one-shape check
ENGINE = "party.ecdsa"

# the round programs (``jit_<name>`` in a device trace) with their limb
# multiply-adds, all of them and the MXU's part; the programs of wire
# rounds 2 and 3 (MtA respond; verify and decrypt)
KERNELS = opcounts.KERNELS
MTA_KERNELS = opcounts.MTA_KERNELS
ops_per_wave = opcounts.per_wave
mxu_ops_per_wave = opcounts.mxu_per_wave

# the group order n (SEC 2, section 2.4.1)
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141

# a CPU rehearsal: a wave of 2 on the 1024-bit fixtures with shrunk proof
# domains (as tests/test_batch_scheduler_ecdsa.py), and still minutes of
# XLA:CPU compile: the slow tier
REHEARSAL = {
    "config": {
        "serving": {"batch_max_batch": 2},
        "population": {"wallets": 2},
        "scheme": {"paillier_bits": 1024,
                   "proof_domains": {"alpha": 600, "beta_prime": 320,
                                     "gamma_bob": 600}},
    },
    "slow": True,
}


def _point(scalar: int) -> bytes:
    """scalar * G, compressed, by OpenSSL."""
    return ec.derive_private_key(scalar, ec.SECP256K1()).public_key() \
        .public_bytes(serialization.Encoding.X962,
                      serialization.PublicFormat.CompressedPoint)


def _domains(config: dict):
    """The proof domains: the program's defaults unless the configuration
    gives sizes of its own (only a rehearsal's overlay does)."""
    from mpcium_tpu.engine.gg18_batch import Domains

    sizes = config["scheme"].get("proof_domains")
    return Domains(**sizes) if sizes else Domains()


def preparam_fixtures(config: dict) -> dict:
    from mpcium_tpu.cluster import load_test_preparams

    return load_test_preparams(bits=config["scheme"].get("paillier_bits", 2048))


# node id -> its batch scheduler's GG18 context cache: the warm batch builds
# each node's modulus contexts where the served batches will find them
_CONTEXTS: Dict[str, object] = {}


def on_cluster(cluster, config: dict) -> None:
    _CONTEXTS.clear()
    for consumer in cluster.consumers:
        scheduler = consumer.scheduler
        _CONTEXTS[scheduler.node.node_id] = scheduler.gg18_contexts()
        if config["scheme"].get("proof_domains"):
            scheduler.gg18_dom = _domains(config)


def _aux(nid: str, node_ids: Sequence[str], preparams: dict) -> dict:
    """What a GG18 keygen leaves with a share: the node's own Paillier key
    and ring-Pedersen parameters, and every peer's public ones."""
    def ring(p):
        return {"ntilde": str(p.NTilde), "h1": str(p.h1), "h2": str(p.h2)}

    peers = [p for p in node_ids if p != nid]
    return {
        "paillier_sk": preparams[nid].paillier.to_json(),
        "preparams": ring(preparams[nid]),
        "peer_paillier": {p: str(preparams[p].paillier.N) for p in peers},
        "peer_ring_pedersen": {p: ring(preparams[p]) for p in peers},
    }


def make_wallets(n_wallets: int, node_ids: Sequence[str], threshold: int,
                 rng: random.Random, preparams: dict
                 ) -> Tuple[List[bytes], Dict[str, list]]:
    """-> (public keys, {node id: the ``KeygenShare`` record of each
    wallet, as the node stores it}). A wallet's key is the constant term
    of a seeded polynomial mod n; its public key and the Feldman
    commitments of the coefficients are OpenSSL's scalar multiples of G."""
    from mpcium_tpu.protocol.base import KeygenShare, party_xs

    xs = party_xs(node_ids)
    participants = sorted(node_ids)
    aux = {nid: _aux(nid, node_ids, preparams) for nid in node_ids}
    pubkeys: List[bytes] = []
    records: Dict[str, list] = {nid: [] for nid in node_ids}
    for _ in range(n_wallets):
        coeffs = [rng.randrange(1, N) for _ in range(threshold + 1)]
        commitments = [_point(c) for c in coeffs]
        pubkeys.append(commitments[0])
        for nid in node_ids:
            share = 0
            for c in reversed(coeffs):
                share = (share * xs[nid] + c) % N
            records[nid].append(KeygenShare(
                key_type=KEY_TYPE, share=share, self_x=xs[nid],
                public_key=commitments[0], vss_commitments=list(commitments),
                participants=participants, threshold=threshold,
                aux=aux[nid]))
    return pubkeys, records


def result_signature(ev) -> str:
    """The result event carries ``r`` and ``s`` apart: as one hex string."""
    return ev.r + ev.s


def verifies(public_key: bytes, digest: bytes, signature: bytes) -> bool:
    """OpenSSL's ECDSA verification of ``r || s`` over the digest as it
    is (no import of the program)."""
    if len(signature) != 64 or len(digest) != 32:
        return False
    r = int.from_bytes(signature[:32], "big")
    s = int.from_bytes(signature[32:], "big")
    try:
        ec.EllipticCurvePublicKey.from_encoded_point(
            ec.SECP256K1(), public_key).verify(
                utils.encode_dss_signature(r, s), digest,
                ec.ECDSA(utils.Prehashed(hashes.SHA256())))
    except (InvalidSignature, ValueError):
        return False
    return True


def high_s(signature: bytes) -> bool:
    return int.from_bytes(signature[32:], "big") > N // 2


def check_rows(served, run) -> dict:
    """The signatures are normalised to the low half of the order; and a
    window too short for the cell (under two counted waves: with one there
    is no growth to read and one reading of the throughput) is a number
    beside its limit, not a name missing from the line."""
    high = sum(1 for r in run.measured if r.success and high_s(r.signature))
    return {
        "high_s_signatures": (high, "==", 0),
        "counted_waves_short_of_two": (
            max(0, 2 - len(run.measured_waves)), "==", 0),
    }


def warm_party(session_id: str, self_id: str, party_ids: Sequence[str],
               shares: list, digests: List[bytes], cohorts: int,
               config: dict):
    """One signer's side of the warm batch (``Served.warm`` runs them)."""
    from mpcium_tpu.protocol.ecdsa.batch_signing import (
        BatchedECDSASigningParty,
    )

    return BatchedECDSASigningParty(session_id, self_id, party_ids, shares,
                                    digests, dom=_domains(config),
                                    contexts=_CONTEXTS.get(self_id))
