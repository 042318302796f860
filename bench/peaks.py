"""Published peaks of each device kind (peaks.json). A device that is
not in the table is an error, never a default."""
from __future__ import annotations

import json

from bench import BENCH


def lookup(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]
