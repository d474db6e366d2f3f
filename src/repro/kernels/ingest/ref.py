"""XLA oracle for the fused ingest kernel.

This IS the split two-pass sequence the kernel fuses — the ring scatter
(:func:`repro.core.storage.ring_ingest`) followed by the bucket pre-agg
merge (:func:`repro.core.preagg.bucket_ingest`) — exposed over raw state
arrays so the kernel layer stays free of store classes.  The Pallas path
must match it bit-for-bit (tier-1 asserts it across shards {1,4,8}).
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.core import preagg as pg
from repro.core import storage as st

__all__ = ["fused_ingest_ref"]


def fused_ingest_ref(
    ring_ts: jnp.ndarray,    # (K, C) int32
    ring_vals: jnp.ndarray,  # (F, K, C) f32 (stored layout)
    cursor: jnp.ndarray,     # (K,) int32
    bstats: jnp.ndarray,     # (F, NUM_STATS, K, NB) f32
    bbitmap: jnp.ndarray,    # (F, K, NB) int32
    bbucket: jnp.ndarray,    # (K, NB) int32
    key: jnp.ndarray,        # (N,) int32 sorted by (key, ts); pad key == K
    ts: jnp.ndarray,         # (N,) int32
    vals: jnp.ndarray,       # (N, F) f32
    *,
    bucket_size: int,
) -> Tuple[jnp.ndarray, ...]:
    ring = st.RingStore(ts=ring_ts, vals=ring_vals, cursor=cursor)
    bagg = pg.BucketAgg(
        stats=bstats, bitmap=bbitmap, bucket=bbucket, size=bucket_size
    )
    ring = st.ring_ingest(ring, key, ts, vals)
    bagg = pg.bucket_ingest(bagg, key, ts, vals)
    return ring.ts, ring.vals, ring.cursor, bagg.stats, bagg.bitmap, bagg.bucket
