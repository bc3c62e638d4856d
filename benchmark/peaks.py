"""The table of peaks (peaks.json), read by device kind."""
from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def for_device(device_kind: str) -> dict:
    with open(_PATH) as fh:
        table = json.load(fh)
    entry = table.get(device_kind)
    if device_kind.startswith("_") or not isinstance(entry, dict):
        raise KeyError(f"no published peaks for device kind {device_kind!r}: "
                       f"add it to {_PATH} with its source")
    return entry
