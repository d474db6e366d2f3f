"""Pure-jnp oracles for the window-aggregation kernels.

* :func:`window_stats_ref` — the pre-aggregated multi-window query: given
  the online store's ring buffers + bucket pre-aggregates and a batch of
  request rows, compute for every (query, window, lane) the five-stat
  vector (sum, count, min, max, sumsq) over the RANGE window ending at the
  request (inclusive of the request row) — the exact semantics of
  ``OnlineFeatureStore``'s pre-agg query path.
* :func:`fold_levels_ref` — the offline segmented-combine scan: all
  doubling levels of a segmented idempotent fold (min / max / bitwise-or),
  the hot loop of ``windows.segmented_windowed_fold``.  Level ``k`` holds
  the combine over ``[max(i - 2^k + 1, seg_start_i), i]`` for every row;
  each level is one *static* shift (pad + slice — never a gather, which is
  what made the old sparse-table formulation compile minutes-slow) plus
  one elementwise combine.
"""

from __future__ import annotations

import math
from typing import Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.storage import cell_index

POS_INF = np.float32(3.0e38)
NEG_INF = np.float32(-3.0e38)

__all__ = [
    "window_stats_ref",
    "fold_levels_ref",
    "fold_num_levels",
    "fold_identity",
    "fold_op",
    "POS_INF",
    "NEG_INF",
]


# segmented idempotent combines the fold kernel supports
_FOLD_OPS = {
    "min": jnp.minimum,
    "max": jnp.maximum,
    "or": jnp.bitwise_or,
}


def fold_op(op: str):
    return _FOLD_OPS[op]


def fold_identity(op: str, dtype) -> jnp.ndarray:
    if op == "min":
        return POS_INF.astype(dtype)
    if op == "max":
        return NEG_INF.astype(dtype)
    if op == "or":
        return jnp.zeros((), dtype)
    raise ValueError(f"unknown fold op {op!r}")


def fold_num_levels(n: int) -> int:
    """Number of doubling levels for ``n`` rows (level 0 = the rows)."""
    return max(1, int(math.floor(math.log2(max(n, 1)))) + 1)


def fold_levels_ref(
    x: jnp.ndarray,    # (N,) f32 (min/max) or int32 (or)
    seg: jnp.ndarray,  # (N,) int32 — each row's key-segment start index
    op: str,
) -> jnp.ndarray:
    """Returns (KL, N): level k = op over [max(i - 2^k + 1, seg_i), i]."""
    n = x.shape[0]
    ident = fold_identity(op, x.dtype)
    f = _FOLD_OPS[op]
    idx = jnp.arange(n, dtype=jnp.int32)
    levels = [x]
    k = 0
    while (1 << (k + 1)) <= max(n, 1):
        half = 1 << k
        prev = levels[-1]
        shifted = jnp.concatenate(
            [jnp.full((half,), ident, x.dtype), prev[:-half]]
        )
        shifted = jnp.where(idx - half >= seg, shifted, ident)
        levels.append(f(prev, shifted))
        k += 1
    return jnp.stack(levels, 0)


def window_stats_ref(
    ring_ts: jnp.ndarray,      # (K, C) int32 (slot order arbitrary)
    ring_lanes: jnp.ndarray,   # (L, K, C) f32 (stored layout)
    bagg_stats: jnp.ndarray,   # (L, 5, K, NB) f32 (stored layout)
    bagg_bucket: jnp.ndarray,  # (K, NB) int32 (-1 empty)
    q_key: jnp.ndarray,        # (Q,) int32
    q_ts: jnp.ndarray,         # (Q,) int32
    q_lanes: jnp.ndarray,      # (Q, L) f32 request-row lane values
    windows: Sequence[int],
    bucket_size: int,
) -> jnp.ndarray:
    """Returns (Q, NW, L, 5) composed stats."""
    B = jnp.int32(bucket_size)
    def rows(x, width):  # (Q, width, *small) per-key rows, logical order
        kk = jnp.broadcast_to(q_key[:, None], (q_key.shape[0], width))
        ss = jnp.broadcast_to(jnp.arange(width, dtype=jnp.int32), kk.shape)
        return x[cell_index(kk, ss, x.shape[:-2])]

    ts = ring_ts[q_key]                               # (Q, C)
    lanes = rows(ring_lanes, ring_ts.shape[1])        # (Q, C, L)
    bstats = rows(bagg_stats, bagg_bucket.shape[1])   # (Q, NB, L, 5)
    bids = bagg_bucket[q_key]                         # (Q, NB)
    valid = ts != jnp.int32(-2147483648)
    bucket_row = ts // B

    outs = []
    for T in windows:
        T = jnp.int32(T)
        lo = q_ts - T + 1
        b_q = q_ts // B
        b_lo = (q_ts - T) // B
        not_future = ts <= q_ts[:, None]
        in_lo = ts >= lo[:, None]
        head = (
            valid & not_future & in_lo
            & (bucket_row == b_lo[:, None]) & (b_lo != b_q)[:, None]
        )
        tail = valid & not_future & in_lo & (bucket_row == b_q[:, None])
        raw = head | tail
        rawf = raw.astype(jnp.float32)[..., None]  # (Q, C, 1)

        g = lanes
        s_raw = jnp.stack(
            [
                (g * rawf).sum(axis=1) + q_lanes,
                rawf.sum(axis=1) + 1.0,
                jnp.minimum(
                    jnp.where(rawf > 0, g, POS_INF).min(axis=1), q_lanes
                ),
                jnp.maximum(
                    jnp.where(rawf > 0, g, NEG_INF).max(axis=1), q_lanes
                ),
                (g * g * rawf).sum(axis=1) + q_lanes * q_lanes,
            ],
            axis=-1,
        )  # (Q, L, 5)

        mid_ok = (bids > b_lo[:, None]) & (bids < b_q[:, None])  # (Q, NB)
        mo = mid_ok[..., None, None]
        s_mid = jnp.stack(
            [
                jnp.where(mo[..., 0], bstats[..., 0], 0.0).sum(axis=1),
                jnp.where(mo[..., 0], bstats[..., 1], 0.0).sum(axis=1),
                jnp.where(mo[..., 0], bstats[..., 2], POS_INF).min(axis=1),
                jnp.where(mo[..., 0], bstats[..., 3], NEG_INF).max(axis=1),
                jnp.where(mo[..., 0], bstats[..., 4], 0.0).sum(axis=1),
            ],
            axis=-1,
        )  # (Q, L, 5)

        s = jnp.stack(
            [
                s_raw[..., 0] + s_mid[..., 0],
                s_raw[..., 1] + s_mid[..., 1],
                jnp.minimum(s_raw[..., 2], s_mid[..., 2]),
                jnp.maximum(s_raw[..., 3], s_mid[..., 3]),
                s_raw[..., 4] + s_mid[..., 4],
            ],
            axis=-1,
        )
        outs.append(s)
    return jnp.stack(outs, axis=1)  # (Q, NW, L, 5)
