"""Published per-chip peaks, keyed by the `device_kind` JAX reports."""

from __future__ import annotations

import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def device_peaks(device_kind):
    """The peaks row for `device_kind`; an unlisted kind is an error,
    never a default."""
    with open(_TABLE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise RuntimeError(
            "no published peaks for device kind %r (peaks.json lists %s): "
            "add its row, with the source, before reporting a share of a "
            "peak on it" % (device_kind, sorted(table)))
    return table[device_kind]
