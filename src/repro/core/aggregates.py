"""Unified aggregator algebra — one monoid spec per ``Agg``.

FeatInsight's offline/online consistency guarantee (§2(3)) only holds if
every execution path computes *the same function*.  OpenMLDB enforces that
by executing one SQL plan everywhere; this reproduction previously defined
each aggregate's semantics four separate times (offline prefix sums and a
sparse table in :mod:`~repro.core.windows`, bucket stats in
:mod:`~repro.core.preagg`, and naive/pre-agg/union branches in
:mod:`~repro.core.online`) — the exact inconsistency trap the paper's
architecture exists to avoid.

This module is now the single source of truth.  Every ``Agg`` is described
by one algebraic spec:

    init      — the identity state
    lift      — row -> state
    combine   — associative state merge
    finalize  — state -> feature value

and every layer is a *strategy for evaluating folds of that monoid*:

* offline batch scan   — segmented prefix sums (invertible lanes),
  segmented doubling folds (idempotent lanes / bitmaps), or closed forms
  (boundary rows, window tails);
* online naive         — fold over masked ring rows;
* online pre-agg       — fold over raw boundary rows ⊕ per-bucket partial
  states (the bucket store literally persists ``combine``-able states);
* WINDOW UNION         — fold across per-stream partial states;
* sharded plane        — the same folds vmapped over shards.

State families (one per representation, shared by several aggs):

``lanes``    a product of scalar lane monoids (sum, count, min, max,
             sumsq) — SUM/COUNT/MEAN/MIN/MAX/STD each select the lanes
             they need and share one lane definition;
``bitmap``   32-bit linear-counting OR-bitmap — DISTINCT_APPROX;
``extreme``  argmin/argmax by the merge order (ts, stream-rank, slot) —
             FIRST (oldest wins) and LAST (newest wins), which makes
             FIRST union-composable: combining per-stream oldest rows
             yields the merged stream's oldest row;
``tail``     the newest ``TOPN_TAIL`` rows by merge order, a mergeable
             sketch (top-k by (ts, rank, pos) of a union is associative)
             — TOPN_FREQ, now union-composable too.

All four families are bucket-composable: the bucket store persists stat
vectors and bitmaps for the lane/bitmap aggs, and per-bucket extreme /
tail states (with a per-key arrival counter as the stored ``pos``) for
FIRST / LAST / TOPN_FREQ — so every aggregate answers long RANGE windows
from pre-aggregates and ``preagg_fallback_total`` stays at zero.

The merge order matches :func:`repro.core.join.merge_streams`: at equal
timestamps, earlier streams (union tables, in declaration order) sort
*before* later ones, and the primary stream is last; within a stream,
arrival order breaks ties.  Cross-stream combines therefore compare
``(ts, rank, pos)`` lexicographically.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.expr import Agg
from repro.core.hashing import mix64

__all__ = [
    "LANES",
    "NUM_STATS",
    "POS_INF",
    "NEG_INF",
    "TOPN_TAIL",
    "AggSpec",
    "AGG_SPECS",
    "agg_spec",
    "lane_identity",
    "lane_lift",
    "lane_combine",
    "lane_masked_reduce",
    "lane_scatter_kind",
    "lanes_identity_stack",
    "lanes_lift_stack",
    "lanes_combine_stack",
    "row_bitmap",
    "bitmap_estimate",
    "topn_rank",
]

POS_INF = np.float32(3.0e38)
NEG_INF = np.float32(-3.0e38)
_TS_MIN = np.int32(-2147483648)
_TS_MAX = np.int32(2147483647)

TOPN_TAIL = 32  # contract: TOPN_FREQ windows are evaluated over <=32 rows

# ---------------------------------------------------------------------------
# Lane monoids — the shared scalar algebra behind SUM/COUNT/MEAN/MIN/MAX/STD
# and the bucket pre-aggregate store (one stat vector per (key, bucket)).
# ---------------------------------------------------------------------------

# stat-lane order == the bucket store's trailing axis layout
LANES: Tuple[str, ...] = ("sum", "count", "min", "max", "sumsq")
NUM_STATS = len(LANES)

_LANE_IDENT = {
    "sum": np.float32(0.0),
    "count": np.float32(0.0),
    "min": POS_INF,
    "max": NEG_INF,
    "sumsq": np.float32(0.0),
}

_LANE_LIFT = {
    "sum": lambda v: v,
    "count": lambda v: jnp.ones_like(v),
    "min": lambda v: v,
    "max": lambda v: v,
    "sumsq": lambda v: v * v,
}

_LANE_COMBINE = {
    "sum": jnp.add,
    "count": jnp.add,
    "min": jnp.minimum,
    "max": jnp.maximum,
    "sumsq": jnp.add,
}

def _tree_sum(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Sum over ``axis`` as an explicit pairwise tree (zero-padded to a
    power of two).  A ``reduce`` leaves the f32 association to the
    compiler, which picks it per program; the tree fixes it, so every
    compiled query (fused multi-scenario, per-scenario, per-shard) adds a
    row's values in the same order and agrees bit for bit."""
    x = jnp.moveaxis(x, axis, -1)
    n = x.shape[-1]
    m = 1 << max(n - 1, 0).bit_length()
    if m != n:
        x = jnp.concatenate(
            [x, jnp.zeros(x.shape[:-1] + (m - n,), x.dtype)], axis=-1
        )
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = x[..., :h] + x[..., h:]
    return x[..., 0]


# axis reduction consistent with each lane's combine (a combine tree over
# one array axis; order-free lanes use the compiler's reduce)
_LANE_REDUCE = {
    "sum": _tree_sum,
    "count": _tree_sum,
    "min": jnp.min,
    "max": jnp.max,
    "sumsq": _tree_sum,
}

# scatter flavour consistent with each lane's combine (``.at[...].<kind>``)
# — how the bucket store merges lifted rows into persisted states
_LANE_SCATTER = {
    "sum": "add",
    "count": "add",
    "min": "min",
    "max": "max",
    "sumsq": "add",
}

# lanes whose lifted states form a *group* (combine is invertible): the
# offline engine may evaluate their window folds as prefix-sum differences
INVERTIBLE_LANES = ("sum", "count", "sumsq")
# lanes whose combine is idempotent: overlapping-range decompositions are
# valid (the doubling-fold query may use two overlapping power-of-two spans)
IDEMPOTENT_LANES = ("min", "max")


def lane_identity(lane: str) -> jnp.ndarray:
    return _LANE_IDENT[lane]


def lane_lift(lane: str, v: jnp.ndarray) -> jnp.ndarray:
    return _LANE_LIFT[lane](v)


def lane_combine(lane: str, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return _LANE_COMBINE[lane](a, b)


def lane_scatter_kind(lane: str) -> str:
    return _LANE_SCATTER[lane]


def lane_masked_reduce(
    lane: str, lifted: jnp.ndarray, mask: jnp.ndarray, axis: int
) -> jnp.ndarray:
    """Fold lifted states over ``axis``, masked rows contributing identity."""
    return _LANE_REDUCE[lane](
        jnp.where(mask, lifted, _LANE_IDENT[lane]), axis=axis
    )


def lanes_lift_stack(v: jnp.ndarray) -> jnp.ndarray:
    """(...,) values -> (..., NUM_STATS) full stat-vector states (the bucket
    store's row lift — buckets persist every lane so any agg can compose)."""
    return jnp.stack([_LANE_LIFT[l](v) for l in LANES], axis=-1)


def lanes_identity_stack(shape: Tuple[int, ...]) -> jnp.ndarray:
    """(shape, NUM_STATS) identity stat vectors."""
    out = jnp.zeros(shape + (NUM_STATS,), jnp.float32)
    for i, l in enumerate(LANES):
        if l in ("min", "max"):
            out = out.at[..., i].set(_LANE_IDENT[l])
    return out


def lanes_combine_stack(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Associative combine of full stat vectors (..., NUM_STATS)."""
    return jnp.stack(
        [
            _LANE_COMBINE[l](a[..., i], b[..., i])
            for i, l in enumerate(LANES)
        ],
        axis=-1,
    )


# ---------------------------------------------------------------------------
# Bitmap monoid — 32-bit linear counting (DISTINCT_APPROX)
# ---------------------------------------------------------------------------


def row_bitmap(vals: jnp.ndarray) -> jnp.ndarray:
    """Per-value 32-bit linear-counting bitmap contribution (the lift)."""
    return (jnp.int32(1) << mix64(vals, salt=77, bits=5)).astype(jnp.int32)


def bitmap_estimate(bits: jnp.ndarray) -> jnp.ndarray:
    """Linear-counting estimate from an OR-combined bitmap (the finalize)."""
    ones = jax.lax.population_count(bits).astype(jnp.float32)
    frac = jnp.clip(ones / 32.0, 0.0, 1.0 - 1e-6)
    return -32.0 * jnp.log1p(-frac)


def _or_reduce(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    return jax.lax.reduce(x, jnp.int32(0), jax.lax.bitwise_or, (axis,))


# ---------------------------------------------------------------------------
# Merge-order helpers (extreme / tail states)
# ---------------------------------------------------------------------------


def _lex_newer(a, b):
    """True where state-b's (ts, rank, pos) is strictly newer than a's."""
    return (
        (b["ts"] > a["ts"])
        | ((b["ts"] == a["ts"]) & (b["rank"] > a["rank"]))
        | (
            (b["ts"] == a["ts"])
            & (b["rank"] == a["rank"])
            & (b["pos"] > a["pos"])
        )
    )


def _desc_argsort(x: jnp.ndarray) -> jnp.ndarray:
    """Stable descending argsort of int32 keys (~x is monotone-decreasing
    and overflow-free, unlike -x at INT32_MIN)."""
    return jnp.argsort(~x, axis=-1, stable=True)


def _sort_tail_desc(state: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Order tail entries newest-first by (ts, rank, pos); invalid last.

    LSD radix of stable argsorts (pos, then rank, then ts), matching
    :func:`repro.core.join.merge_streams`'s tie rule exactly.
    """
    ts = jnp.where(state["valid"], state["ts"], _TS_MIN)
    rank = jnp.where(state["valid"], state["rank"], jnp.int32(-1))
    pos = jnp.where(state["valid"], state["pos"], _TS_MIN)

    def take(d, order):
        return {k: jnp.take_along_axis(v, order, axis=-1) for k, v in d.items()}

    cur = dict(state, ts=ts, rank=rank, pos=pos)
    for field in ("pos", "rank", "ts"):  # least-significant first
        cur = take(cur, _desc_argsort(cur[field]))
    return cur


def topn_rank(
    vals: jnp.ndarray, valid: jnp.ndarray, nth: int
) -> jnp.ndarray:
    """n-th most-frequent value over newest-first tail entries.

    ``vals``/``valid``: (..., T) with slot 0 the most recent entry.  Ranking
    rule (shared verbatim by offline, online, union, sharded): frequency
    desc, value asc, duplicate occurrences deduped to their most recent
    slot.  Returns 0.0 where fewer than ``nth + 1`` distinct values exist.
    """
    tail = vals.shape[-1]
    eq = (
        (vals[..., :, None] == vals[..., None, :])
        & valid[..., :, None]
        & valid[..., None, :]
    )
    freq = eq.sum(-1).astype(jnp.float32)
    freq = jnp.where(valid, freq, -1.0)
    earlier = jnp.tril(jnp.ones((tail, tail), bool), -1)
    same_as_earlier = (eq & earlier).any(-1)
    is_first = valid & ~same_as_earlier
    score = jnp.where(is_first, freq, -1.0)
    # rank by (freq desc, value asc) — composed into one sortable score
    vmax = jnp.max(jnp.abs(vals), initial=1.0)
    composite = score * (2.0 * vmax + 1.0) - vals
    order = jnp.argsort(-composite, axis=-1)
    pick = order[..., nth]
    picked_score = jnp.take_along_axis(score, pick[..., None], axis=-1)[..., 0]
    val = jnp.take_along_axis(vals, pick[..., None], axis=-1)[..., 0]
    return jnp.where(picked_score >= 0.0, val, 0.0)


# ---------------------------------------------------------------------------
# The spec
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AggSpec:
    """One aggregate's algebra: (init, lift, combine, finalize) + layout.

    States are dicts of arrays (pytrees), so one spec serves scalars,
    per-query batches, per-shard stacks, and bucket grids alike:

    ``lanes``:    {lane: (...,)}                     (selected stat lanes)
    ``bitmap``:   {"bits": (...,) int32}
    ``extreme``:  {"ts", "rank", "pos", "val", "has"}
    ``tail``:     {"ts", "rank", "pos", "val", "valid"}  each (..., T)
    """

    agg: Agg
    state: str                       # "lanes" | "bitmap" | "extreme" | "tail"
    lanes: Tuple[str, ...] = ()      # state == "lanes": which lanes
    newest: bool = False             # state == "extreme": LAST (vs FIRST)
    union_composable: bool = True
    bucket_composable: bool = False  # state persisted by the bucket store

    # -- init ---------------------------------------------------------------

    def init(self, shape: Tuple[int, ...] = ()) -> Dict[str, jnp.ndarray]:
        """Identity state of batch ``shape``."""
        if self.state == "lanes":
            return {
                l: jnp.broadcast_to(_LANE_IDENT[l], shape) for l in self.lanes
            }
        if self.state == "bitmap":
            return {"bits": jnp.zeros(shape, jnp.int32)}
        if self.state == "extreme":
            return {
                "ts": jnp.broadcast_to(_TS_MIN, shape),
                "rank": jnp.zeros(shape, jnp.int32),
                "pos": jnp.zeros(shape, jnp.int32),
                "val": jnp.zeros(shape, jnp.float32),
                "has": jnp.zeros(shape, bool),
            }
        # tail: zero-width entry set
        return {
            "ts": jnp.zeros(shape + (0,), jnp.int32),
            "rank": jnp.zeros(shape + (0,), jnp.int32),
            "pos": jnp.zeros(shape + (0,), jnp.int32),
            "val": jnp.zeros(shape + (0,), jnp.float32),
            "valid": jnp.zeros(shape + (0,), bool),
        }

    # -- lift ---------------------------------------------------------------

    def lift(
        self,
        val: jnp.ndarray,
        ts: jnp.ndarray,
        rank: jnp.ndarray,
        pos: jnp.ndarray,
    ) -> Dict[str, jnp.ndarray]:
        """Single row -> state.  ``(ts, rank, pos)`` is the row's merge-order
        coordinate (ignored by lanes/bitmap states)."""
        if self.state == "lanes":
            return {l: _LANE_LIFT[l](val) for l in self.lanes}
        if self.state == "bitmap":
            return {"bits": row_bitmap(val)}
        if self.state == "extreme":
            return {
                "ts": jnp.broadcast_to(ts, val.shape),
                "rank": jnp.broadcast_to(rank, val.shape),
                "pos": jnp.broadcast_to(pos, val.shape),
                "val": val,
                "has": jnp.ones(val.shape, bool),
            }
        return {
            "ts": jnp.broadcast_to(ts, val.shape)[..., None],
            "rank": jnp.broadcast_to(rank, val.shape)[..., None],
            "pos": jnp.broadcast_to(pos, val.shape)[..., None],
            "val": val[..., None],
            "valid": jnp.ones(val.shape + (1,), bool),
        }

    # -- combine ------------------------------------------------------------

    def combine(
        self, a: Dict[str, jnp.ndarray], b: Dict[str, jnp.ndarray]
    ) -> Dict[str, jnp.ndarray]:
        """Associative merge of two states."""
        if self.state == "lanes":
            return {l: _LANE_COMBINE[l](a[l], b[l]) for l in self.lanes}
        if self.state == "bitmap":
            return {"bits": a["bits"] | b["bits"]}
        if self.state == "extreme":
            if self.newest:
                pick_b = ~a["has"] | (b["has"] & _lex_newer(a, b))
            else:
                pick_b = ~a["has"] | (b["has"] & ~_lex_newer(a, b))
            pick_b = pick_b & b["has"]
            out = {
                k: jnp.where(pick_b, b[k], a[k])
                for k in ("ts", "rank", "pos", "val")
            }
            out["has"] = a["has"] | b["has"]
            return out
        # tail: union of entry sets, keep the TOPN_TAIL newest by merge order
        cat = {
            k: jnp.concatenate([a[k], b[k]], axis=-1)
            for k in ("ts", "rank", "pos", "val", "valid")
        }
        merged = _sort_tail_desc(cat)
        if merged["ts"].shape[-1] > TOPN_TAIL:
            merged = {k: v[..., :TOPN_TAIL] for k, v in merged.items()}
        return merged

    # -- fold strategies (shared by the online naive/pre-agg/union paths) ---

    def fold_rows(
        self,
        g: jnp.ndarray,       # (Q, C) lane values
        ts: jnp.ndarray,      # (Q, C) row timestamps
        mask: jnp.ndarray,    # (Q, C) in-window mask
        rank: jnp.ndarray,    # scalar int32 — the buffer's stream rank
    ) -> Dict[str, jnp.ndarray]:
        """Fold one ring buffer's masked rows into a state (axis 1).

        The buffer is slot-ordered oldest -> newest, so the slot index is
        the within-stream merge coordinate ``pos``.
        """
        C = g.shape[1]
        if self.state == "lanes":
            return {
                l: lane_masked_reduce(l, _LANE_LIFT[l](g), mask, 1)
                for l in self.lanes
            }
        if self.state == "bitmap":
            return {
                "bits": _or_reduce(
                    jnp.where(mask, row_bitmap(g), jnp.int32(0)), 1
                )
            }
        if self.state == "extreme":
            if self.newest:
                ts_m = jnp.where(mask, ts, _TS_MIN)
                best = jnp.max(ts_m, axis=1)
                cand = mask & (ts == best[:, None])
                pos = C - 1 - jnp.argmax(cand[:, ::-1], axis=1)
            else:
                ts_m = jnp.where(mask, ts, _TS_MAX)
                best = jnp.min(ts_m, axis=1)
                cand = mask & (ts == best[:, None])
                pos = jnp.argmax(cand, axis=1).astype(jnp.int32)
            val = jnp.take_along_axis(g, pos[:, None], axis=1)[:, 0]
            return {
                "ts": best,
                "rank": jnp.broadcast_to(rank, best.shape),
                "pos": pos.astype(jnp.int32),
                "val": val,
                "has": mask.any(axis=1),
            }
        # tail: the newest (TOPN_TAIL - 1) slots, masked — enough because a
        # merged tail of T rows takes at most T-1 from any one stream once
        # the request row is counted (matching the pre-algebra behaviour)
        t = min(TOPN_TAIL - 1, C)
        sl = slice(C - t, C)
        pos = jnp.arange(C, dtype=jnp.int32)[sl][::-1]
        return {
            "ts": jnp.broadcast_to(ts[:, sl][:, ::-1], mask[:, sl].shape),
            "rank": jnp.broadcast_to(rank, (g.shape[0], t)),
            "pos": jnp.broadcast_to(pos, (g.shape[0], t)),
            "val": g[:, sl][:, ::-1],
            "valid": mask[:, sl][:, ::-1],
        }

    def fold_buckets(
        self,
        stats: Dict[str, jnp.ndarray],  # {lane: (Q, M)} bucket stat rows
        bitmap: jnp.ndarray,  # (Q, M) bucket bitmaps (bitmap specs)
        ok: jnp.ndarray,      # (Q, M) bucket-valid mask
        ext: Dict[str, jnp.ndarray] = None,  # persisted extreme/tail rows
        rank: jnp.ndarray = None,            # stream rank to stamp on states
    ) -> Dict[str, jnp.ndarray]:
        """Fold pre-aggregated bucket states (bucket_composable specs only).

        The bucket store persists full stat vectors and bitmaps — i.e. the
        lifted-and-combined states of this algebra — so composing a long
        window is just more ``combine``.  Lane specs read ``stats[lane]``
        for each lane they select.  Extreme/tail specs read their
        persisted merge-order states from ``ext`` instead: for extreme,
        ``{ts, pos, val, has}`` each (Q, M), the winner in this spec's
        direction; for tail, ``{ts, pos, val, valid}`` each (Q, M, T)
        newest-first per bucket.  Buckets cover disjoint ts ranges, so
        cross-bucket ties never happen and the stored per-key arrival
        ``pos`` only ever breaks ties within one bucket — where it is
        exact.
        """
        if self.state == "lanes":
            return {
                l: lane_masked_reduce(l, stats[l], ok, 1)
                for l in self.lanes
            }
        if self.state == "bitmap":
            return {
                "bits": _or_reduce(jnp.where(ok, bitmap, jnp.int32(0)), 1)
            }
        if ext is None:
            raise ValueError(
                f"{self.agg} bucket states need the store's extreme/tail "
                "arrays (layout planned without them)"
            )
        if self.state == "extreme":
            ts, pos, val = ext["ts"], ext["pos"], ext["val"]
            has = ext["has"] & ok
            if self.newest:
                ts_m = jnp.where(has, ts, _TS_MIN)
                best_ts = jnp.max(ts_m, axis=1)
                cand = has & (ts == best_ts[:, None])
                pos_m = jnp.where(cand, pos, _TS_MIN)
                best_pos = jnp.max(pos_m, axis=1)
            else:
                ts_m = jnp.where(has, ts, _TS_MAX)
                best_ts = jnp.min(ts_m, axis=1)
                cand = has & (ts == best_ts[:, None])
                pos_m = jnp.where(cand, pos, _TS_MAX)
                best_pos = jnp.min(pos_m, axis=1)
            pick = jnp.argmax(cand & (pos == best_pos[:, None]), axis=1)
            v = jnp.take_along_axis(val, pick[:, None], axis=1)[:, 0]
            return {
                "ts": best_ts,
                "rank": jnp.broadcast_to(rank, best_ts.shape),
                "pos": best_pos,
                "val": v,
                "has": has.any(axis=1),
            }
        # tail: every gathered bucket's tail entries, newest TOPN_TAIL kept
        flat = lambda x: x.reshape(x.shape[0], -1)  # noqa: E731
        valid = flat(ext["valid"] & ok[..., None])
        state = {
            "ts": flat(ext["ts"]),
            "rank": jnp.broadcast_to(rank, valid.shape),
            "pos": flat(ext["pos"]),
            "val": flat(ext["val"]),
            "valid": valid,
        }
        merged = _sort_tail_desc(state)
        if merged["ts"].shape[-1] > TOPN_TAIL:
            merged = {k: v[..., :TOPN_TAIL] for k, v in merged.items()}
        return merged

    # -- finalize -----------------------------------------------------------

    def finalize(self, s: Dict[str, jnp.ndarray], n: int = 0) -> jnp.ndarray:
        """State -> feature value (the one definition every path shares)."""
        a = self.agg
        if a == Agg.SUM:
            return s["sum"]
        if a == Agg.COUNT:
            return s["count"]
        if a == Agg.MEAN:
            return s["sum"] / jnp.maximum(s["count"], 1.0)
        if a == Agg.MIN:
            return s["min"]
        if a == Agg.MAX:
            return s["max"]
        if a == Agg.STD:
            cnt = jnp.maximum(s["count"], 1.0)
            m = s["sum"] / cnt
            return jnp.sqrt(jnp.maximum(s["sumsq"] / cnt - m * m, 0.0))
        if a == Agg.DISTINCT_APPROX:
            return bitmap_estimate(s["bits"])
        if a in (Agg.FIRST, Agg.LAST):
            return s["val"]
        if a == Agg.TOPN_FREQ:
            return topn_rank(s["val"], s["valid"], n)
        raise ValueError(f"unhandled agg {a}")


# ---------------------------------------------------------------------------
# The registry — exactly one spec per Agg
# ---------------------------------------------------------------------------

AGG_SPECS: Dict[Agg, AggSpec] = {
    Agg.SUM: AggSpec(Agg.SUM, "lanes", lanes=("sum",), bucket_composable=True),
    Agg.COUNT: AggSpec(
        Agg.COUNT, "lanes", lanes=("count",), bucket_composable=True
    ),
    Agg.MEAN: AggSpec(
        Agg.MEAN, "lanes", lanes=("sum", "count"), bucket_composable=True
    ),
    Agg.MIN: AggSpec(Agg.MIN, "lanes", lanes=("min",), bucket_composable=True),
    Agg.MAX: AggSpec(Agg.MAX, "lanes", lanes=("max",), bucket_composable=True),
    Agg.STD: AggSpec(
        Agg.STD, "lanes", lanes=("sum", "count", "sumsq"),
        bucket_composable=True,
    ),
    Agg.DISTINCT_APPROX: AggSpec(
        Agg.DISTINCT_APPROX, "bitmap", bucket_composable=True
    ),
    Agg.FIRST: AggSpec(
        Agg.FIRST, "extreme", newest=False, bucket_composable=True
    ),
    Agg.LAST: AggSpec(
        Agg.LAST, "extreme", newest=True, bucket_composable=True
    ),
    Agg.TOPN_FREQ: AggSpec(Agg.TOPN_FREQ, "tail", bucket_composable=True),
}


def agg_spec(agg: Agg) -> AggSpec:
    return AGG_SPECS[agg]
