"""Peaks of the chip and the bytes the hop program needs.

The peaks table is ``bench/peaks.json``, keyed by ``device_kind`` as JAX
reports it, with its source; a kind that is not in it is an error.
"""

from __future__ import annotations

import json
import os

from .spec import BENCH

__all__ = ["peaks", "hop_bytes"]


def peaks(device_kind: str) -> dict:
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {', '.join(table)})")
    return table[device_kind]


def hop_bytes(dist_evals: int, hops: int, d: int, degree: int) -> int:
    """HBM bytes a beam-search hop needs at the least: each scored row
    read once (``d`` float32) and each expanded node's adjacency row read
    once (``degree`` int32).  Counted from the algorithm's own counters,
    so it is the same whatever implements the hop."""
    return int(dist_evals) * d * 4 + int(hops) * degree * 4
