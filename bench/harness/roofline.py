"""Peaks of the chip and the bytes the hop program needs.

The peaks table is ``bench/peaks.json``, keyed by ``device_kind`` as JAX
reports it, with its source; a kind that is not in it is an error.
"""

from __future__ import annotations

import json
import os

from .spec import BENCH, dqf_config

__all__ = ["peaks", "row_bytes", "hop_bytes"]


def peaks(device_kind: str) -> dict:
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {', '.join(table)})")
    return table[device_kind]


def row_bytes(config: dict) -> int:
    """HBM bytes the hop reads for one scored row of the configuration's
    Full Index, by the width of what it stores: ``dim`` float32 without a
    ``quant`` block; ``dim`` one-byte codes under ``sq8`` (its scale and
    offset are one row for all, not a read per row); ``pq_m`` one-byte
    codes under ``pq`` (its lookup tables are per query).  A tiered Full
    Index raises: its rows come through a host fetch, which no HBM
    roofline reckons."""
    c = dqf_config(config)
    if c.tier.mode != "none":
        raise ValueError(f"no HBM row bytes for tier mode {c.tier.mode!r}")
    d = int(config["dim"])
    return {"none": d * 4, "sq8": d, "pq": c.quant.pq_m}[c.quant.mode]


def hop_bytes(dist_evals: int, hops: int, row_bytes: int,
              degree: int) -> int:
    """HBM bytes a beam-search hop needs at the least: each scored row
    read once (``row_bytes``, from :func:`row_bytes`) and each expanded
    node's adjacency row read once (``degree`` int32).  Counted from the
    algorithm's own counters, so it is the same whatever implements the
    hop."""
    return int(dist_evals) * int(row_bytes) + int(hops) * degree * 4
