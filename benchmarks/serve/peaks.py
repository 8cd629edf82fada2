"""Published peaks of each chip, keyed by JAX's ``device_kind``.

A device missing from ``peaks.json`` is an error, never a default.
"""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(TABLE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"{TABLE.name}; known: {sorted(table)}")
    return table[device_kind]
