"""Least bytes the nucleus split's kernels must move, from their shapes.

As in ``bench/counts.py``: every input read once from HBM and every output
written once, whatever implements the operation, so a roofline share from
these cannot pass 100%.
"""
from __future__ import annotations

from bench.counts import I32


def edt_bytes(h: int, w: int) -> int:
    """Squared distance transform: int32 mask in, int32 squared distances out."""
    return 2 * I32 * h * w


def watershed_bytes(h: int, w: int) -> int:
    """Watershed (its cost reconstruction, label propagation and relabelling):
    int32 squared distances and marker labels in, int32 labels out."""
    return 3 * I32 * h * w
