"""The table of peaks (``peaks.json``), keyed by JAX's ``device_kind``."""
from __future__ import annotations

from typing import Dict

from benchmarks.chip.spec import HERE, load_json


def peaks(device_kind: str) -> Dict[str, float]:
    table = load_json(HERE / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]
