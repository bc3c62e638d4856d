"""``quorum.select_ms_per_wave`` in the GG18 cell that serves below n-of-n: the
``host:quorum_select`` spans (choosing who signs a batch: here a secp256k1
bucket under the deputy's manifest), a node and wave, by the sibling
reader's own arithmetic (``quorum.select_ms_per_wave.py``, loaded and not
copied: that entry lists its own cells, and a list cannot be joined later)."""

import os

from benchmark import harness

read = harness._load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "quorum.select_ms_per_wave.py")).read
