"""Traffic of kind ``cold_sweep``: the closed loop of ``closed_waves.py``
(one client, whole waves of distinct wallets, the next wave when the last
result is in) over a population far larger than a run can sign for, so
that every share read in the window is its wallet's first since the node
started. The loop is the one beside this file, loaded and not copied: for
a seed, this kind sends the very waves ``closed_waves`` sends.

A deployment's population has to be sealed into every node's share store
before the first wave, a put a wallet and node. A program whose store
rewrites its whole name index on every put (``mpcium_tpu/store/kvstore.py``
before ``STORE_FORMAT`` 2: ~0.84 us a name already held, each put) would
spend hours there, so this file asks the store for its format when it is
LOADED and refuses such a program: the cell then fails at
``harness.Cell(...)``, in seconds, before a wallet is made.
"""
from __future__ import annotations

import os

from benchmark import harness
from mpcium_tpu.store import kvstore

NEEDS_STORE_FORMAT = 2

if getattr(kvstore, "STORE_FORMAT", 1) < NEEDS_STORE_FORMAT:
    raise RuntimeError(
        "traffic of kind cold_sweep needs a share store whose put does not "
        "rewrite its whole name index (mpcium_tpu.store.kvstore.STORE_FORMAT "
        f">= {NEEDS_STORE_FORMAT}; this program has "
        f"{getattr(kvstore, 'STORE_FORMAT', 'none: format 1')}): sealing a "
        "deployment's population into it would take hours")


drive = harness._load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "closed_waves.py")).drive
