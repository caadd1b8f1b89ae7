"""Published peaks of the accelerators the benchmark runs on (``peaks.json``,
keyed by JAX's ``device_kind``).  A kind that is not in the table is an
error, never a default."""

from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str, path: str = PATH) -> dict:
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
