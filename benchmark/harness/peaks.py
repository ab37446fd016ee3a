"""The table of device peaks, keyed by `device_kind`. An unknown device
is an error, never a default: a roofline share against a guessed peak is
worse than none."""

from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_of(device_kind: str) -> dict:
    with open(PATH) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(f"no peaks on record for device kind {device_kind!r} "
                       f"(benchmark/harness/peaks.json has {sorted(table)})")
    return table[device_kind]
