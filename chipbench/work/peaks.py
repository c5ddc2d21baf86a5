"""Published peaks per chip, keyed by JAX's ``device_kind``.  A device
that is not in ``peaks.json`` is an error, never a default."""

from __future__ import annotations

import json
from pathlib import Path

_TABLE = Path(__file__).with_name("peaks.json")


def peaks_for(device_kind: str) -> dict:
    table = json.loads(_TABLE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" in {_TABLE.name}; add them with their source")
    return table[device_kind]
