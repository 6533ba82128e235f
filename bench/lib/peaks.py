"""Published per-chip peaks, keyed by JAX's ``device_kind``."""
from __future__ import annotations

from pathlib import Path
from typing import Dict

from spec import SpecError, load_json

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; a device not in the table is an error."""
    table = load_json(PEAKS_FILE)["devices"]
    if device_kind not in table:
        raise SpecError(f"no published peaks for device kind {device_kind!r} "
                        f"(known: {sorted(table)}); add them to {PEAKS_FILE.name}")
    return {k: float(v) for k, v in table[device_kind].items()}
