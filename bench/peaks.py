"""Published peaks of each chip, keyed by JAX's ``device_kind``
(``peaks.json``). A device that is not in the table is an error."""
from __future__ import annotations

import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


class UnknownDevice(KeyError):
    """The device kind has no row in the peaks table."""


def peaks(device_kind: str) -> dict:
    with open(_TABLE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; known: "
            f"{sorted(table)}")
    return table[device_kind]
