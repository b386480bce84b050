"""The chip's published peaks, keyed by JAX's ``device_kind``."""

from __future__ import annotations

import json
from pathlib import Path

_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str, path: Path = _FILE) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error,
    never a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path} "
                       f"(known: {sorted(table)})")
    return table[device_kind]
