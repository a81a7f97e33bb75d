"""Published peaks of one chip, keyed by JAX's `device_kind`
(`bench/peaks.json`, with its source).  A device that is not in the table
is an error, never a default."""

from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parents[1] / "peaks.json"


def of(device_kind: str) -> dict:
    devices = json.loads(TABLE.read_text())["devices"]
    if device_kind not in devices:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(devices)}")
    return devices[device_kind]
