"""Published peaks of each accelerator, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(LookupError):
    """The device kind has no row in ``peaks.json``."""


def peaks_for(kind: str, table: Path = TABLE) -> dict:
    rows = json.loads(table.read_text())
    if kind not in rows:
        raise UnknownDevice(f"no peaks for device kind {kind!r} in {table.name}"
                            f" (known: {sorted(rows)})")
    return rows[kind]
