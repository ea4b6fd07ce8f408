"""The chip's published peaks, keyed by JAX's ``device_kind``."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peak(device_kind: str) -> dict:
    """Peaks of one device kind; an unknown device is an error."""
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device {device_kind!r} in "
                       "bench/peaks.json")
    return table[device_kind]
