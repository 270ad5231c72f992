"""Published peaks of each chip the benchmark runs on, keyed by JAX's
``device_kind`` (``peaks.json``).  A kind missing from the table is an
error, not a default."""

from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peak(device_kind: str) -> dict:
    with open(PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PATH}; known: {sorted(table)}")
    return table[device_kind]
