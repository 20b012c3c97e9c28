"""The table of hardware peaks, keyed by the exact ``device_kind`` string
JAX reports. A device that is not in the table is an error, never a
default: a utilisation against a guessed peak is not a measurement."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
FIELDS = ("bf16_flops_per_s", "hbm_bytes_per_s", "ici_bits_per_s",
          "hbm_bytes", "source")


def load(path: str = _PATH) -> dict:
    with open(path) as f:
        return json.load(f)


def for_device_kind(kind: str, table: dict | None = None) -> dict:
    table = load() if table is None else table
    if kind not in table:
        raise KeyError(
            f"device_kind {kind!r} is not in {_PATH} (known: "
            f"{sorted(table)}); add it with its source, do not guess")
    entry = table[kind]
    missing = [k for k in FIELDS if k not in entry]
    if missing:
        raise KeyError(f"peaks entry {kind!r} lacks {missing}")
    return entry
