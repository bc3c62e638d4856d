"""``gg18.achieved_gops`` in the GG18 cell that serves below n-of-n: the round
programs' limb multiply-adds over their device time in the traced wave, by
the sibling reader's own arithmetic (``gg18.achieved_gops.py``, loaded and
not copied: that entry lists its own cells, and a list cannot be joined
later). The counted work is the scheme file's at ``run.quorum`` signers
(``per_wave(wave, q)``: q (q - 1) ordered MtA pairs, q nodes' curve
programs), so the reading is of the same counted work whatever implements
it."""

import os

from benchmark import harness

read = harness._load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "gg18.achieved_gops.py")).read
