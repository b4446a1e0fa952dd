"""The chip's peaks, from ``bench/peaks.json``, keyed by the device kind
that JAX reports. A run on a chip checks that its kind is in the table;
a roofline or a share of a peak reads its numbers from there.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; a kind not in the table is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name} (known: {', '.join(table)})")
    return table[device_kind]
