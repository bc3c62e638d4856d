"""Traffic of kind ``node_down_warm_waves``: ``node_down_waves`` (beside this
file, loaded and not copied: the nodes the configuration names are stopped,
the live registries settle, ``closed_waves``' loop runs, the stopped nodes'
books are held still) with one more step of set-up between the settling and
the first wave: a warm batch of the nodes that will SERVE.

``Served.warm`` warms the first ``served_quorum`` nodes of the committee,
which are not the live ones when a node of low rank is out. Where a
scheme's programs are shaped by its parties' keys (GG18: a round program's
operands follow its moduli's limb counts, and a node's scheduler keeps a
modulus context a committee member), the first served batch would then
build the live committee's contexts and meet its programs cold from the
node threads, inside a wave's time limit. This kind runs that batch
first, the way ``Served.warm`` does: party level, in the main thread,
through the synchronous in-process runner, on the live nodes' own shares
(read back from their sealed stores: the first wave-size wallets') and
fixed digests, the scheme file building each party (for GG18 on that
node's own context cache). It is part of set-up: it ends before the
unmeasured wave starts, and a mix with no unmeasured wave is refused.
"""
from __future__ import annotations

import os
from typing import Callable, List, Optional

from benchmark import harness

_node_down = harness._load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "node_down_waves.py"))


def _warm_live(served, live: List[str]) -> None:
    """One party-level batch of ``live`` at the cell's shape."""
    from mpcium_tpu.protocol.runner import run_protocol

    if len(live) != served.quorum:
        raise RuntimeError(
            f"the configuration serves at quorum {served.quorum}, and "
            f"{len(live)} of its nodes stay up: {live}")
    key_type = served.config["scheme"]["key_type"]
    wallets = served.wallet_ids[: served.wave_size]
    digests = [bytes([i % 256]) * served.digest_bytes
               for i in range(served.wave_size)]
    parties = {
        pid: served.scheme.warm_party(
            "bench-warm-live", pid, live,
            [served.cluster.nodes[pid].load_share(key_type, w)
             for w in wallets],
            digests, served.cohorts, served.config)
        for pid in live
    }
    run_protocol(parties)
    for pid, p in parties.items():
        if not bool(p.result["ok"].all()):
            raise RuntimeError(
                f"the live nodes' warm batch failed verification at {pid}")


def drive(served, params: dict, seed: int, seconds: float,
          on_wave: Optional[Callable] = None,
          before_wave: Optional[Callable] = None) -> dict:
    if int(params["unmeasured_waves"]) < 1:
        raise ValueError(
            "traffic of kind node_down_warm_waves warms the live nodes "
            "before the first wave, which has to be an unmeasured one")
    down = _node_down._down_nodes(params, served)
    live = [nid for nid in served.cluster.node_ids if nid not in down]

    def warm_first(index: int, measured: bool) -> None:
        if index == 0:  # the nodes have left and the registries agree
            _warm_live(served, live)
        if before_wave is not None:
            before_wave(index, measured)

    return _node_down.drive(served, params, seed, seconds,
                            on_wave=on_wave, before_wave=warm_first)
