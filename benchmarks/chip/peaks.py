"""Published peaks of each device kind (``peaks.json``)."""

from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str, key: str) -> float:
    """One peak of ``device_kind``; a kind the table lacks is an error,
    never a default."""
    kinds = json.loads(TABLE.read_text())["kinds"]
    if device_kind not in kinds:
        raise ValueError(f"no peaks for device kind {device_kind!r} in "
                         f"{TABLE.name}")
    return float(kinds[device_kind][key])
