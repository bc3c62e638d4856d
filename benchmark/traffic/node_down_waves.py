"""Traffic of kind ``node_down_waves``: the closed loop of ``closed_waves.py``
(one client, whole waves of distinct wallets, the next wave when the last
result is in) against a cluster some of whose nodes have LEFT: before the
first wave each node the configuration names (``layout.down_nodes``) is
stopped the way a daemon stops on SIGTERM (``LocalCluster.stop_node``: its
consumers closed, its ready key resigned, its sealed store left on disk),
and the generator waits until every live node's registry lists exactly the
live nodes. The loop is the one beside this file, loaded and not copied:
for a seed, this kind sends the very waves ``closed_waves`` sends.

After the window it holds the run to what "the node was out" means: a
stopped node's scheduler took in nothing and fired nothing and its share
store was not read since the stop, and no manifest anywhere waited out a
timeout for a deputy to take it over. A run that breaks either prints no
result.

A program that cannot stop a node is refused when this file is LOADED: the
cell then fails at ``harness.Cell(...)``, in seconds, before a wallet is
made.
"""
from __future__ import annotations

import os
import time
from typing import Callable, List, Optional, Tuple

from benchmark import harness
from mpcium_tpu.cluster import LocalCluster

if not hasattr(LocalCluster, "stop_node"):
    raise RuntimeError(
        "traffic of kind node_down_waves needs a cluster whose nodes can "
        "leave (mpcium_tpu.cluster.LocalCluster.stop_node: this program has "
        "none): a node could only be crashed through a fault plan, which "
        "the served cluster is built without")

_closed_waves = harness._load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "closed_waves.py"))


def _down_nodes(params: dict, served) -> List[str]:
    """The nodes to stop: a list, or ``"<group>.<key>"`` of the
    configuration that holds one."""
    named = params["down_nodes"]
    if isinstance(named, str):
        group, key = named.split(".")
        named = served.config[group][key]
    return list(named)


def _settle(cluster, live: List[str], timeout_s: float) -> None:
    """Wait until every live node's registry lists exactly ``live``."""
    deadline = time.monotonic() + timeout_s
    while True:
        views = {nid: cluster.nodes[nid].registry.ready_peers()
                 for nid in live}
        if all(view == sorted(live) for view in views.values()):
            return
        if time.monotonic() >= deadline:
            raise RuntimeError(
                f"after {timeout_s} s the live registries do not list "
                f"exactly {sorted(live)}: {views}")
        time.sleep(0.01)


def _books(served, nid: str) -> Tuple[float, float, int]:
    """What a node that is out must not move: requests its scheduler took
    in, manifests it fired, reads of its share store."""
    snap = served.metrics_snapshot()[nid]
    return (snap["counters"].get("scheduler.submitted_total", 0.0),
            snap["counters"].get("scheduler.batches_fired_total", 0.0),
            snap["histograms"].get("store.get_s", {}).get("count", 0))


def drive(served, params: dict, seed: int, seconds: float,
          on_wave: Optional[Callable] = None,
          before_wave: Optional[Callable] = None) -> dict:
    cluster = served.cluster
    down = _down_nodes(params, served)
    live = [nid for nid in cluster.node_ids if nid not in down]
    for nid in down:
        cluster.stop_node(nid)
    _settle(cluster, live, float(params["settle_timeout_s"]))
    at_stop = {nid: _books(served, nid) for nid in down}
    driven = _closed_waves.drive(served, params, seed, seconds,
                                 on_wave=on_wave, before_wave=before_wave)
    at_end = {nid: _books(served, nid) for nid in down}
    moved = {nid: (at_stop[nid], at_end[nid]) for nid in down
             if at_end[nid] != at_stop[nid]}
    if moved:
        raise RuntimeError(
            "a stopped node took part in the run (submitted, manifests "
            f"fired, share reads; at the stop, at the end): {moved}")
    takeovers = served.counter_total("scheduler.deputy_takeover_total")
    if takeovers:
        raise RuntimeError(
            f"{int(takeovers)} manifests waited out a timeout for a deputy: "
            "the live nodes' registries did not agree on who was out")
    return driven
