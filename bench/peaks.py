"""The chip's published peaks, one entry per JAX ``device_kind`` (``peaks.json``).

A device that is not in the table is an error, never a default: a rate or a
share against the wrong chip's peak would be a wrong number."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict

TABLE = Path(__file__).resolve().with_name("peaks.json")


def lookup(device_kind: str, table: Path = TABLE) -> Dict[str, Any]:
    with open(table) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {table.name}; known: {sorted(peaks)}")
    return peaks[device_kind]
