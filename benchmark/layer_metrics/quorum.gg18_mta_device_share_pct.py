"""``gg18.mta_device_share_pct`` in the GG18 cell that serves below n-of-n: of
all programs' device time in the traced wave, the share of the MtA programs
of wire rounds 2 and 3, by the sibling reader's own arithmetic
(``gg18.mta_device_share_pct.py``, loaded and not copied: that entry lists
its own cells, and a list cannot be joined later)."""

import os

from benchmark import harness

read = harness._load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "gg18.mta_device_share_pct.py")).read
