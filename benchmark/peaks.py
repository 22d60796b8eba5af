"""The table of chip peaks, keyed by ``device_kind``.  A device that is not
in the table is an error: no default, no environment override."""

from __future__ import annotations

import json
import os

_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(LookupError):
    pass


def load_peaks(device_kind: str) -> dict:
    with open(_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in benchmark/peaks.json "
            f"(known: {sorted(table)}); add its published peaks there")
    return table[device_kind]
