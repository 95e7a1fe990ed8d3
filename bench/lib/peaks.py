"""The chip peaks table (``bench/peaks.json``), keyed by ``device_kind``."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


class UnknownDevice(KeyError):
    """A device kind with no entry in the peaks table."""


def peaks(device_kind: str, table: dict | None = None) -> dict:
    """The peaks of ``device_kind``; an unknown device is an error, never
    a default."""
    table = json.loads(PEAKS_FILE.read_text()) if table is None else table
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r}; "
                            f"known: {sorted(table)}")
    return table[device_kind]
