"""Bytes the leaf dominance scan needs, for its roofline share.

The scan is bound by memory, not by arithmetic: per (query path, index
row) pair it makes about one compare per float it reads.  So its least
time is its bytes over the chip's HBM bandwidth.  The bytes are counted at
the configuration's unpadded widths, the payload the algorithm needs, not
the 128-lane tiles a layout pads them to: a better layout then reads as a
higher share, not as a smaller count.

Per pair the scan reads the index row's dominance vector of
``(l+1)·d·(1+n)`` float32 (main and extra GNNs), its label vector of
``(l+1)·d`` float32, and the row's ``l+1`` int32 path vertices that a kept
pair hands to the join.  The query side is read once per probe, not per
pair, and is left out.
"""
from __future__ import annotations

F32 = 4
I32 = 4


def leaf_scan_bytes_per_pair(path_length: int, emb_dim: int, n_multi: int) -> int:
    width = (path_length + 1) * emb_dim
    return F32 * width * (1 + n_multi) + F32 * width + I32 * (path_length + 1)


def leaf_scan_bytes(pairs: float, path_length: int, emb_dim: int, n_multi: int) -> float:
    return pairs * leaf_scan_bytes_per_pair(path_length, emb_dim, n_multi)


def roofline_share(bytes_moved: float, seconds: float, peaks: dict) -> float | None:
    """Least time over measured time, in %; None where nothing ran."""
    if seconds <= 0 or bytes_moved <= 0:
        return None
    return 100.0 * (bytes_moved / peaks["hbm_bytes_per_s"]) / seconds
