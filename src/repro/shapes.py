"""Padded shapes for jitted calls and Pallas kernels.

Ragged sizes (partitions, candidate sets, pair counts) pad up to a power
of two so a jitted call compiles a handful of shapes instead of one per
size (on a TPU each compile takes seconds), and kernel feature dims pad
to whole 128-wide lanes.
"""
from __future__ import annotations

import numpy as np

__all__ = ["pow2_at_least", "lanes", "pad_rows"]


def pow2_at_least(n: int, floor: int = 8) -> int:
    """The least ``floor · 2^k`` (k ≥ 0) that is ≥ ``n``."""
    out = floor
    while out < n:
        out *= 2
    return out


def lanes(n: int) -> int:
    """``n`` rounded up to whole 128-wide lanes."""
    return -(-n // 128) * 128


def pad_rows(a: np.ndarray, n: int) -> np.ndarray:
    """``a`` with zero rows appended up to ``n`` rows."""
    a = np.asarray(a)
    return np.pad(a, [(0, n - a.shape[0])] + [(0, 0)] * (a.ndim - 1))
