"""The chip peaks the readers divide by, and the width of a bf16 value.

A model kind's operations and bytes are its module's ``Work``
(``references/<kind>.py``).
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
BF16 = 2


def peaks_for(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
