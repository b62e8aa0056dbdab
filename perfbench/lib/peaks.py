"""The one table of device peaks, keyed by ``device_kind``. A device that
is not in it is an error, never a default."""

from __future__ import annotations

import json
import os


def load() -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json"),
              encoding="utf-8") as f:
        return json.load(f)


def for_kind(kind: str) -> dict:
    table = load()
    if kind not in table:
        raise KeyError(f"device kind {kind!r} has no row in perfbench/lib/"
                       f"peaks.json (has: {sorted(table)})")
    return table[kind]
