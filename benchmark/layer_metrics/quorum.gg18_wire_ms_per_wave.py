"""``gg18.wire_ms_per_wave`` in the GG18 cell that serves below n-of-n: the
GG18 session's host wire stage (each ``round:gg18/b/*`` span's self time
less its ``phase:gg18_*`` children, plus ``host:envelope_in``), a node and
wave, over the nodes that sign, by the sibling reader's own arithmetic
(``gg18.wire_ms_per_wave.py``, loaded and not copied: that entry lists its
own cells, and a list cannot be joined later)."""

import os

from benchmark import harness

read = harness._load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "gg18.wire_ms_per_wave.py")).read
