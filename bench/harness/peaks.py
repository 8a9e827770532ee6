"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind`` (``bench/peaks.json``).  A device missing from the table
is an error, never a default."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

TABLE = Path(__file__).resolve().parents[1] / "peaks.json"


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str, table: Path = TABLE) -> Dict[str, float]:
    known = json.loads(table.read_text())
    if device_kind not in known:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r}; "
                            f"known: {sorted(known)}")
    return known[device_kind]
