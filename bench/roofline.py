"""The peaks table and the work a query launch needs, from its shapes.

The work is what the algorithm needs, whatever implements it: one
exact scan of a chip's live points for a batch of ``rows`` real queries
costs ``2 * rows * points * dim`` flops and reads each point and its id
once (``points * (dim * 4 + 4)`` bytes).  Padding, copies and
intermediate matrices are the implementation's and are not counted, so a
share of the roofline says how far the launch is from the least time
the chip could take.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """``{"flops_per_s", "bytes_per_s"}`` of one chip of this kind."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to {PEAKS.name} with its source")
    return table[device_kind]


def query_work(rows: float, points: int, dim: int) -> tuple:
    """(flops, bytes) of one launch over one chip's live points."""
    return 2.0 * rows * points * dim, points * (dim * 4.0 + 4.0)


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """Seconds the chip needs at least: the larger of the two bounds."""
    return max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
