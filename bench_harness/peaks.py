"""Peaks of each chip, keyed by JAX's ``device_kind``, from peaks.json.
A kind that is not in the table is an error, never a default."""

from __future__ import annotations

import json
import os

TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def lookup(kind: str) -> dict:
    with open(TABLE) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]
