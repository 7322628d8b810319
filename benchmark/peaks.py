"""The table of peaks, keyed by ``device_kind``. An unknown kind is an error:
a utilisation over a guessed peak is not a measurement."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)
    try:
        return table["kinds"][device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"{_PATH}; add it with its source, do not assume one"
        ) from None
