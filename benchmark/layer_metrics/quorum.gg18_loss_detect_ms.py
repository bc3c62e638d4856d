"""``registry.loss_detect_ms`` in the GG18 cell that serves below n-of-n: the
mean time a live node's registry took to drop the peer that left
(``registry.loss_detect_s`` at the window's start: the departure is part of
set-up), by the sibling reader's own arithmetic
(``registry.loss_detect_ms.py``, loaded and not copied: that entry lists its
own cells, and a list cannot be joined later)."""

import os

from benchmark import harness

read = harness._load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "registry.loss_detect_ms.py")).read
