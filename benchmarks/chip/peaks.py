"""Published peaks of each chip, keyed by JAX's ``device_kind``
(``peaks.json``, with its source).  A device not in the table is an
error, never a default."""

from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def lookup(kind: str) -> dict[str, float]:
    with open(TABLE) as f:
        devices = json.load(f)["devices"]
    if kind not in devices:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"the table has {sorted(devices)}")
    return devices[kind]
