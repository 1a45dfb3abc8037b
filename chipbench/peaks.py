"""Published peaks of one chip, keyed by JAX's ``device_kind``.

The table is ``peaks.json`` beside this file, with its source. A device
kind that is not in the table is an error: a share of a peak is never
computed against a guessed or default peak.
"""
from __future__ import annotations

import json
from pathlib import Path

_TABLE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    table = json.loads(_TABLE.read_text())
    if device_kind not in table or device_kind.startswith("_"):
        known = sorted(k for k in table if not k.startswith("_"))
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {known}")
    return table[device_kind]
