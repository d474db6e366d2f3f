"""Two-level window pre-aggregation — FeatInsight's long-window optimization.

The paper: "we apply pre-aggregation to handle long time intervals (e.g.,
for years) or hotspot data".  OpenMLDB materializes per-bucket partial
aggregates so a long RANGE window composes O(window/bucket) bucket aggs plus
two raw boundary scans, instead of scanning every raw row.

This module is now *only the bucket store*: a dense per-key ring of
persisted aggregate **states** of the algebra in
:mod:`repro.core.aggregates` — the full stat-lane vector (sum, count, min,
max, sumsq) plus the 32-bit distinct bitmap per (key, bucket, field),
maintained by the same fused-scatter ingest as the row store.  How those
states compose into window answers lives with the aggregator specs
(``AggSpec.fold_buckets`` / ``combine`` / ``finalize``), consumed by
:class:`repro.core.online.OnlineFeatureStore` — there is no aggregate
semantics here to drift out of sync.

A query composes:

    [raw tail rows in the newest partial bucket]      (scan, <= bucket rows)
  + [full buckets strictly inside the window]         (combine, <= NB aggs)
  + [raw head rows in the oldest partial bucket]      (scan, <= bucket rows)

For exact offline<->online consistency the raw ring must retain the boundary
buckets' rows; the middle composes losslessly because bucket rows are
``combine``-able states (sums associative, min/max/bitmap idempotent).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregates as ag
from repro.core.storage import cell_index, to_logical, to_stored
from repro.core.aggregates import (
    LANES,
    NEG_INF,
    NUM_STATS,
    POS_INF,
    TOPN_TAIL,
    row_bitmap,
)

__all__ = [
    "BucketAgg",
    "bucket_init",
    "bucket_init_plan",
    "bucket_ingest",
    "bucket_to_host",
    "bucket_from_host",
    "row_stats",
    "stats_identity",
    "row_bitmap",
    "NUM_STATS",
    "POS_INF",
    "NEG_INF",
]

# lift / identity for the persisted full stat vector come straight from the
# lane monoids — the bucket store stores algebra states, nothing else
row_stats = ag.lanes_lift_stack
stats_identity = ag.lanes_identity_stack


_TS_EMPTY = np.int32(-2147483648)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BucketAgg:
    """Per-key ring of per-bucket partial aggregate states.

    Arrays are in the stored key-minor layout of :mod:`repro.core.storage`
    (small axes first, then key K, then bucket slot NB):

    stats  : (F, NUM_STATS, K, NB) f32  stat-lane states (aggregates.LANES)
    bitmap : (F, K, NB) int32   32-bit linear-counting bitmap per field
    bucket : (K, NB) int32      absolute bucket id held in each slot (-1 empty)

    Merge-order state families (``None`` unless the layout persists them —
    a view with FIRST/LAST/TOPN_FREQ over a RANGE window):

    seq    : (K,) int32         per-key arrival counter; the stored merge
                                ``pos`` of a row is its per-key arrival
                                index (mirrors the ring cursor)
    xts/xpos/xhas : (2, K, NB)  extreme winner per direction
                                (0 = oldest / FIRST, 1 = newest / LAST);
                                winner row shared across lanes
    xval   : (F, 2, K, NB)      the winner row's lane values
    tts/tpos/tvalid : (T, K, NB) newest-first tail of the bucket's rows
                                by (ts, pos), T = aggregates.TOPN_TAIL
    tval   : (F, T, K, NB)      the tail rows' lane values
    """

    stats: jnp.ndarray
    bitmap: jnp.ndarray
    bucket: jnp.ndarray
    size: int  # bucket width in time units (static)
    seq: Optional[jnp.ndarray] = None
    xts: Optional[jnp.ndarray] = None
    xpos: Optional[jnp.ndarray] = None
    xval: Optional[jnp.ndarray] = None
    xhas: Optional[jnp.ndarray] = None
    tts: Optional[jnp.ndarray] = None
    tpos: Optional[jnp.ndarray] = None
    tval: Optional[jnp.ndarray] = None
    tvalid: Optional[jnp.ndarray] = None

    def tree_flatten(self):
        return (
            self.stats, self.bitmap, self.bucket, self.seq,
            self.xts, self.xpos, self.xval, self.xhas,
            self.tts, self.tpos, self.tval, self.tvalid,
        ), (self.size,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        stats, bitmap, bucket, *rest = children
        return cls(stats, bitmap, bucket, size=aux[0], seq=rest[0],
                   xts=rest[1], xpos=rest[2], xval=rest[3], xhas=rest[4],
                   tts=rest[5], tpos=rest[6], tval=rest[7], tvalid=rest[8])

    @property
    def num_buckets(self) -> int:
        return self.bucket.shape[-1]


def bucket_init(
    num_keys: int, num_buckets: int, width: int, size: int,
    *, extreme: bool = False, tail: bool = False,
) -> BucketAgg:
    K, NB, T = num_keys, num_buckets, TOPN_TAIL
    kw = {}
    if extreme or tail:
        kw["seq"] = jnp.zeros((K,), jnp.int32)
    if extreme:
        kw["xts"] = jnp.full((2, K, NB), _TS_EMPTY)
        kw["xpos"] = jnp.zeros((2, K, NB), jnp.int32)
        kw["xval"] = jnp.zeros((width, 2, K, NB), jnp.float32)
        kw["xhas"] = jnp.zeros((2, K, NB), bool)
    if tail:
        kw["tts"] = jnp.full((T, K, NB), _TS_EMPTY)
        kw["tpos"] = jnp.zeros((T, K, NB), jnp.int32)
        kw["tval"] = jnp.zeros((width, T, K, NB), jnp.float32)
        kw["tvalid"] = jnp.zeros((T, K, NB), bool)
    ident = stats_identity(())  # (NUM_STATS,)
    return BucketAgg(
        stats=jnp.broadcast_to(
            ident[None, :, None, None], (width, NUM_STATS, K, NB)
        ),
        bitmap=jnp.zeros((width, K, NB), jnp.int32),
        bucket=jnp.full((K, NB), -1, jnp.int32),
        size=size,
        **kw,
    )


def bucket_init_plan(plan, num_keys: int, width: int) -> BucketAgg:
    """Initialize a bucket store straight from a declarative
    :class:`~repro.core.layout.BucketPlan` — the store consumes the plan
    instead of re-deriving its sizing (including which merge-order state
    families it persists)."""
    return bucket_init(
        num_keys, plan.num_buckets, width, plan.bucket_size,
        extreme=getattr(plan, "extreme", False),
        tail=getattr(plan, "tail", False),
    )


def bucket_to_host(bagg: BucketAgg, lead: int = 0) -> dict:
    """Every array of a bucket store as host numpy in the logical per-key
    layout ((*batch, K, NB, *small)); absent families are omitted."""
    out = {}
    for name in ("stats", "bitmap", "bucket", "seq", "xts", "xpos", "xval",
                 "xhas", "tts", "tpos", "tval", "tvalid"):
        a = getattr(bagg, name)
        if a is not None:
            out[name] = to_logical(np.asarray(a), lead)
    return out


def bucket_from_host(size: int, arrays: dict, lead: int = 0) -> BucketAgg:
    """Inverse of :func:`bucket_to_host`: logical host arrays -> a device
    bucket store in the stored layout."""
    dt = {"bucket": jnp.int32, "seq": jnp.int32}
    return BucketAgg(
        size=size,
        **{
            k: jnp.asarray(
                np.ascontiguousarray(to_stored(np.asarray(v), lead)),
                dt.get(k),
            )
            for k, v in arrays.items()
        },
    )


def _segment_or_scan(bm: jnp.ndarray, new_seg: jnp.ndarray) -> jnp.ndarray:
    """Inclusive segmented bitwise-OR scan along axis 0."""

    def comb(a, b):
        flag_a, val_a = a
        flag_b, val_b = b
        val = jnp.where(flag_b, val_b, val_a | val_b)
        return flag_a | flag_b, val

    flags = new_seg
    if bm.ndim > 1:
        flags = jnp.broadcast_to(new_seg[:, None], bm.shape)
    _, out = jax.lax.associative_scan(comb, (flags, bm))
    return out


def _lane_scatter(target, index, update, lane_idx: int, lane: str):
    """Merge lifted lane states into stored states with the lane's own
    combine flavour (``.add`` / ``.min`` / ``.max``)."""
    at = target.at[index + (slice(None), lane_idx)]
    kind = ag.lane_scatter_kind(lane)
    if kind == "add":
        return at.add(update, mode="drop")
    if kind == "min":
        return at.min(update, mode="drop")
    return at.max(update, mode="drop")


def _at(x: jnp.ndarray, k: jnp.ndarray, s: jnp.ndarray):
    """``x.at`` over cell (k, s) of every small-axis position of a stored
    (*small, K, NB) array: gathers/sets (N, *small) blocks, one scalar
    gather/scatter each (see :func:`repro.core.storage.cell_index`)."""
    return x.at[cell_index(k, s, x.shape[:-2])]


def bucket_ingest(
    agg: BucketAgg,
    key: jnp.ndarray,   # (N,) int32 sorted by (key, ts)
    ts: jnp.ndarray,    # (N,) int32
    vals: jnp.ndarray,  # (N, F) f32
) -> BucketAgg:
    """Merge an ingest batch into bucket aggregates (one fused pass).

    Constraint (callers assert): a single batch spans fewer than NB buckets,
    so each (key, slot) receives at most one new bucket id.  Slots whose
    stored bucket id differs from the incoming id are reset first (ring
    reuse) — the scatter analogue of OpenMLDB finalizing an old bucket.

    Each valid segment owns a distinct (key, slot) cell, so every state
    array is updated by one gather, a combine, and one set: race-free, and
    bit-identical to scatter-combining into the (reset) stored state.
    Padding/no-op rows route to out-of-bounds keys with mode="drop".
    """
    nb = agg.num_buckets
    K = agg.bucket.shape[0]
    bucket_id = ts // jnp.int32(agg.size)
    slot = bucket_id % nb

    n = key.shape[0]
    new_seg = jnp.concatenate(
        [
            jnp.array([True]),
            (key[1:] != key[:-1]) | (bucket_id[1:] != bucket_id[:-1]),
        ]
    )
    seg_id = jnp.cumsum(new_seg.astype(jnp.int32)) - 1  # (N,), 0..S-1

    rs = row_stats(vals)   # (N, F, NUM_STATS) lifted lane states
    bm = row_bitmap(vals)  # (N, F)

    # --- per-(key,bucket) segment reduction into scratch rows -------------
    width = vals.shape[1]
    seg_stats = stats_identity((n, width))
    for i, lane in enumerate(LANES):
        seg_stats = _lane_scatter(seg_stats, (seg_id,), rs[..., i], i, lane)
    or_scan = _segment_or_scan(bm, new_seg)  # (N, F) inclusive per segment

    # one representative (= last) row per segment
    seg_end = jnp.concatenate([new_seg[1:], jnp.array([True])])
    end_rows = jnp.nonzero(seg_end, size=n, fill_value=0)[0]
    num_segs = seg_id[-1] + 1
    seg_valid = jnp.arange(n, dtype=jnp.int32) < num_segs

    rep_key = key[end_rows]
    rep_slot = slot[end_rows]
    rep_bucket = bucket_id[end_rows]
    rep_stats = seg_stats[jnp.arange(n)]          # row s = segment s's totals
    rep_bm = or_scan[end_rows]

    # out-of-bounds key (=K) for padding rows => dropped by every scatter
    k_v = jnp.where(seg_valid, rep_key, jnp.int32(K))
    s_v = rep_slot

    # --- slots holding a stale bucket restart from the identity ------------
    stored = agg.bucket.at[k_v, s_v].get(mode="fill", fill_value=-1)
    stale = seg_valid & (stored != rep_bucket) & (stored != -1)

    g_stats = _at(agg.stats, k_v, s_v).get(mode="fill", fill_value=0.0)
    g_stats = jnp.where(
        stale[:, None, None], stats_identity((n, width)), g_stats
    )
    stats = _at(agg.stats, k_v, s_v).set(
        ag.lanes_combine_stack(g_stats, rep_stats), mode="drop"
    )
    g_bm = _at(agg.bitmap, k_v, s_v).get(mode="fill", fill_value=0)
    g_bm = jnp.where(stale[:, None], 0, g_bm)
    bitmap = _at(agg.bitmap, k_v, s_v).set(g_bm | rep_bm, mode="drop")

    bucket_ids = agg.bucket.at[k_v, s_v].set(rep_bucket, mode="drop")

    # --- merge-order state families (extreme / tail) -----------------------
    # Presence is a static pytree property, so plain python gating is fine
    # under jit.  Both families key row identity on (ts, pos) where pos is
    # the per-key arrival index: rows are sorted (key, ts) and arrive in
    # batch order, so within a key run pos = seq[key] + rank-in-run.
    seq = agg.seq
    xts, xpos, xval, xhas = agg.xts, agg.xpos, agg.xval, agg.xhas
    tts, tpos, tval, tvalid = agg.tts, agg.tpos, agg.tval, agg.tvalid
    if seq is not None:
        idx = jnp.arange(n, dtype=jnp.int32)
        new_key = jnp.concatenate([jnp.array([True]), key[1:] != key[:-1]])
        run_start = jax.lax.cummax(jnp.where(new_key, idx, 0))
        pos = seq.at[key].get(mode="fill", fill_value=0) + (idx - run_start)
        start_rows = jnp.nonzero(new_seg, size=n, fill_value=0)[0]

    if xts is not None:
        # within a segment ts and pos both ascend, so the lex-oldest row is
        # the segment's first row and the lex-newest its last
        c_rows = jnp.stack([start_rows, end_rows], axis=-1)   # (N, 2)
        c_ts = ts[c_rows]
        c_pos = pos[c_rows]
        c_val = vals[c_rows].transpose(0, 2, 1)               # (N, F, 2)

        st2 = stale[:, None]
        g_ts = _at(xts, k_v, s_v).get(mode="fill", fill_value=_TS_EMPTY)
        g_pos = _at(xpos, k_v, s_v).get(mode="fill", fill_value=0)
        g_val = _at(xval, k_v, s_v).get(mode="fill", fill_value=0.0)
        g_has = _at(xhas, k_v, s_v).get(mode="fill", fill_value=False)
        g_ts = jnp.where(st2, _TS_EMPTY, g_ts)
        g_pos = jnp.where(st2, 0, g_pos)
        g_val = jnp.where(st2[:, None], 0.0, g_val)
        g_has = g_has & ~st2

        older = (c_ts < g_ts) | ((c_ts == g_ts) & (c_pos < g_pos))
        newer = (c_ts > g_ts) | ((c_ts == g_ts) & (c_pos > g_pos))
        want = jnp.stack([older[:, 0], newer[:, 1]], axis=-1)
        take = ~g_has | want                                  # (N, 2)

        xts = _at(xts, k_v, s_v).set(
            jnp.where(take, c_ts, g_ts), mode="drop")
        xpos = _at(xpos, k_v, s_v).set(
            jnp.where(take, c_pos, g_pos), mode="drop")
        xval = _at(xval, k_v, s_v).set(
            jnp.where(take[:, None, :], c_val, g_val), mode="drop")
        xhas = _at(xhas, k_v, s_v).set(jnp.ones((n, 2), bool), mode="drop")

    if tts is not None:
        T = tts.shape[0]
        # newest-first candidate rows of each segment (row order is
        # (ts, pos) ascending, so counting back from end_rows is exact)
        t_rows = end_rows[:, None] - jnp.arange(T, dtype=jnp.int32)[None, :]
        in_seg = t_rows >= start_rows[:, None]                # (N, T)
        t_rc = jnp.clip(t_rows, 0, n - 1)
        ct_ts = jnp.where(in_seg, ts[t_rc], _TS_EMPTY)
        ct_pos = jnp.where(in_seg, pos[t_rc], _TS_EMPTY)
        ct_val = jnp.where(
            in_seg[:, None, :], vals[t_rc].transpose(0, 2, 1), 0.0)

        st2 = stale[:, None]
        gt_ts = _at(tts, k_v, s_v).get(mode="fill", fill_value=_TS_EMPTY)
        gt_pos = _at(tpos, k_v, s_v).get(mode="fill", fill_value=0)
        gt_val = _at(tval, k_v, s_v).get(mode="fill", fill_value=0.0)
        gt_valid = _at(tvalid, k_v, s_v).get(mode="fill", fill_value=False)
        gt_ts = jnp.where(st2, _TS_EMPTY, gt_ts)
        gt_pos = jnp.where(st2, 0, gt_pos)
        gt_val = jnp.where(st2[:, None], 0.0, gt_val)
        gt_valid = gt_valid & ~st2

        m_ts = jnp.concatenate(
            [ct_ts, jnp.where(gt_valid, gt_ts, _TS_EMPTY)], axis=1)
        m_pos = jnp.concatenate(
            [ct_pos, jnp.where(gt_valid, gt_pos, _TS_EMPTY)], axis=1)
        m_val = jnp.concatenate([ct_val, gt_val], axis=2)     # (N, F, 2T)
        m_valid = jnp.concatenate([in_seg, gt_valid], axis=1)

        # LSD stable descending sort by (ts, pos): pos pass, then ts pass
        o1 = jnp.argsort(~m_pos, axis=1, stable=True)
        o2 = jnp.argsort(
            ~jnp.take_along_axis(m_ts, o1, axis=1), axis=1, stable=True)
        perm = jnp.take_along_axis(o1, o2, axis=1)

        s_ts = jnp.take_along_axis(m_ts, perm, axis=1)[:, :T]
        s_pos = jnp.take_along_axis(m_pos, perm, axis=1)[:, :T]
        s_valid = jnp.take_along_axis(m_valid, perm, axis=1)[:, :T]
        s_val = jnp.take_along_axis(
            m_val, perm[:, None, :], axis=2)[:, :, :T]

        tts = _at(tts, k_v, s_v).set(s_ts, mode="drop")
        tpos = _at(tpos, k_v, s_v).set(
            jnp.where(s_valid, s_pos, 0), mode="drop")
        tval = _at(tval, k_v, s_v).set(
            jnp.where(s_valid[:, None, :], s_val, 0.0), mode="drop")
        tvalid = _at(tvalid, k_v, s_v).set(s_valid, mode="drop")

    if seq is not None:
        seq = seq.at[key].add(jnp.ones_like(key), mode="drop")

    return BucketAgg(
        stats=stats, bitmap=bitmap, bucket=bucket_ids, size=agg.size,
        seq=seq, xts=xts, xpos=xpos, xval=xval, xhas=xhas,
        tts=tts, tpos=tpos, tval=tval, tvalid=tvalid,
    )
