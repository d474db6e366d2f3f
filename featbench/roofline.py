"""Bytes each kernel must move for the work it was given.

Counted from a batch's rows, distinct keys and distinct (key, bucket)
pairs, never from the kernel's block shapes: a kernel that moves less
does the same work, so its share of the roofline rises and cannot pass
100% by shrinking its blocks.  4-byte int32 keys, timestamps and slots;
float32 values.
"""

from __future__ import annotations


def fused_ingest_bytes(rows: int, segments: int, lanes: int) -> int:
    """One ingest batch: read each row (key, ts and its lanes), write it
    into its key's ring (ts and lanes); read and write the pre-aggregate
    of each (key, bucket) it touches (five float32 stats and an int32
    bitmap per lane, and the int32 bucket id)."""
    per_row = 4 + 4 + 4 * lanes + 4 + 4 * lanes
    per_segment = 2 * (5 * 4 * lanes + 4 * lanes + 4)
    return rows * per_row + segments * per_segment


def route_rank_bytes(rows: int, shards: int) -> int:
    """One routed batch: read each row's shard id, write its rank within
    its shard, write the per-shard counts."""
    return rows * (4 + 4) + shards * 4


def roofline_share(needed_bytes: float, kernel_s: float,
                   peak_bytes_per_s: float):
    """Least time over measured time, in %, or None where the kernel did
    not run."""
    if kernel_s <= 0 or needed_bytes <= 0:
        return None
    return 100.0 * needed_bytes / peak_bytes_per_s / kernel_s
