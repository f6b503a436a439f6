"""Published peaks of each accelerator, keyed by JAX's ``device_kind``
(``bench/peaks.json``, with the source of each). A device that is not in
the table is an error, never a default."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    table = json.loads(TABLE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {TABLE.name}; known: "
                       f"{sorted(table)}")
    return table[device_kind]
