"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Every share of a peak in the benchmark divides by a number from
``peaks.json``. A device that is not in the table is an error, never a
default.
"""

from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def lookup(kind: str) -> dict:
    """The peaks of one chip of ``kind``; KeyError for an unknown kind."""
    devices = json.loads(TABLE.read_text())["devices"]
    if kind not in devices:
        raise KeyError(f"no published peaks for device kind {kind!r} in {TABLE.name}; "
                       f"known: {sorted(devices)}")
    return devices[kind]
