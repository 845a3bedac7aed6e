"""The table of peaks, keyed by `device_kind`. An unknown device is an error."""

from __future__ import annotations

import os

from .manifest import BENCH_DIR, load_json


def peaks_for(device_kind: str) -> dict:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"perfbench/peaks.json; has "
                       f"{sorted(k for k in table if not k.startswith('_'))}")
    return table[device_kind]
