"""Live-plane state migration — hot deployment as a state transform.

FeatInsight deploys new feature services onto a *running* platform; the
OpenMLDB substrate treats deploying a new computation over warm state as a
first-class operation.  This module is that operation for the JAX stores:
given a :class:`~repro.core.layout.LayoutDiff` (old plan → new plan), it
produces the new :class:`~repro.core.online.OnlineState` from the old one
**without re-ingesting anything**:

* rings whose :meth:`~repro.core.layout.RingPlan.identity` is unchanged
  are carried over verbatim (the device buffers move, zero copy);
* rings whose lane plan grew/permuted get their lanes re-mapped, with new
  lanes *synthesized* by re-evaluating the lane expression over the raw
  column lanes an evolvable layout stores (``raw_lanes=True``);
* rings whose capacity changed are re-laid slot-by-slot (the ring's
  cursor arithmetic is reproduced, so the result is byte-identical to a
  store that ran at the new capacity all along — as long as no row had
  already aged out);
* rings whose *placement* changed (the dual-use split: a replicated table
  becoming a partitioned union ring + a narrow replicated join slice, or
  vice versa) are rebuilt by decoding per-key row streams from the source
  ring and re-encoding them under the new routing — per-key ring state
  depends only on that key's rows and their order, which the transform
  preserves exactly;
* bucket pre-aggregate states carry per lane; states for *new* lanes are
  re-folded from the ring's retained rows with the same left-to-right
  association ``bucket_ingest`` uses.

Exactness contract: the migrated state is **bit-identical** to a cold
rebuild + full replay of the same stream whenever the information still
exists in the store — i.e. no required row has aged out of its ring and
(for synthesized lanes) the layout carries raw-column lanes.  When the
horizon is exceeded the migration still succeeds but flags
``report.exact = False`` with a note naming what was lost; the
hot-deploy CI gate (:mod:`benchmarks.bench_deploy`) runs inside the
horizon and asserts bit-exactness outright.

Beyond-the-horizon migrations close the gap through the **offline
backfill bridge** (:mod:`repro.offline.backfill`): every inexactness
site records a structured :class:`Deficit` naming the state it could not
reconstruct, and :func:`migrate_state` accepts a ``backfill=`` source.
When one is passed, lanes that cannot be synthesized from stored f32
columns (hash/signature exprs, un-materialized raw columns) are
*deferred* — zero-filled and recorded as deficits — instead of refusing,
and the caller (:meth:`OnlineFeatureStore.adopt_layout`) splices
offline-re-derived state over every deficit before the new layout goes
live, restoring ``report.exact``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import storage as st
from repro.core.aggregates import (
    LANES,
    NEG_INF,
    POS_INF,
    TOPN_TAIL,
    row_bitmap,
)
from repro.core.expr import Col, eval_rowlevel
from repro.core.layout import LaneSlot, LayoutDiff, RingPlan
from repro.core.online import OnlineState
from repro.obs import get_telemetry

__all__ = ["Deficit", "MigrationReport", "migrate_state"]

_TS_MIN = np.int32(-2147483648)


@dataclasses.dataclass(frozen=True)
class Deficit:
    """One piece of state a migration could not reconstruct exactly.

    ``target`` is ``'ring'`` or ``'bucket'``; ``ring`` indexes the new
    layout's secondary rings (``None`` = the primary ring / the bucket
    store).  ``lanes`` names the affected lane keys, or ``None`` when the
    whole structure is deficient (aged-out rows, bucket-slot remap after
    wraparound).  Deficits are exactly what the offline backfill bridge
    (:mod:`repro.offline.backfill`) knows how to re-derive from history.
    """

    target: str                       # 'ring' | 'bucket'
    table: str
    ring: Optional[int] = None        # new.tables index; None = primary
    lanes: Optional[Tuple] = None     # affected lane keys; None = all
    reason: str = ""

    def describe(self) -> str:
        what = (
            "all lanes" if self.lanes is None
            else ", ".join(repr(k) for k in self.lanes)
        )
        return f"{self.target} {self.table} [{what}]: {self.reason}"


@dataclasses.dataclass
class MigrationReport:
    """What a layout adoption actually did to the live state."""

    diff_summary: str
    carried: List[str] = dataclasses.field(default_factory=list)
    migrated: List[str] = dataclasses.field(default_factory=list)
    fresh: List[str] = dataclasses.field(default_factory=list)
    dropped: List[str] = dataclasses.field(default_factory=list)
    synthesized_lanes: List[str] = dataclasses.field(default_factory=list)
    new_programs: List[str] = dataclasses.field(default_factory=list)
    exact: bool = True
    notes: List[str] = dataclasses.field(default_factory=list)
    deficits: List[Deficit] = dataclasses.field(default_factory=list)
    backfilled: List[str] = dataclasses.field(default_factory=list)
    # inexactness NOT repairable from offline history (e.g. key-domain
    # shrink dropping out-of-domain rows) — the backfill splice never
    # restores report.exact while this is set
    hard_inexact: bool = False

    def add_deficit(self, d: Deficit) -> None:
        """Record a repairable inexactness: the migration proceeds, the
        report flips inexact, and the deficit tells the backfill bridge
        exactly what to re-derive."""
        self.deficits.append(d)
        self.exact = False
        self.notes.append(d.reason)

    def describe(self) -> str:
        lines = [
            f"migration: {self.diff_summary} "
            f"exact={'yes' if self.exact else 'NO'}"
        ]
        for tag, items in (
            ("carried", self.carried),
            ("migrated", self.migrated),
            ("fresh", self.fresh),
            ("dropped", self.dropped),
            ("synthesized", self.synthesized_lanes),
            ("backfilled", self.backfilled),
            ("new programs", self.new_programs),
        ):
            if items:
                lines.append(f"  {tag}: {', '.join(items)}")
        for d in self.deficits:
            lines.append(f"  deficit: {d.describe()}")
        for n in self.notes:
            lines.append(f"  note: {n}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Host-side ring helpers
# ---------------------------------------------------------------------------


def _host_ring(ring: st.RingStore, sharded: bool):
    """Pull a ring to host as (ts (S,K,C), vals (S,K,C,F), cur (S,K)) —
    a leading singleton shard axis is added for unsharded stores so every
    transform below is shard-shape-agnostic."""
    ts = np.asarray(ring.ts)
    vals = st.to_logical(np.asarray(ring.vals), int(sharded))
    cur = np.asarray(ring.cursor)
    if not sharded:
        ts, vals, cur = ts[None], vals[None], cur[None]
    return ts, vals, cur


def _mk_ring(ts, vals, cur, sharded: bool) -> st.RingStore:
    """Inverse of :func:`_host_ring`: logical host arrays -> device ring
    in the stored layout."""
    if not sharded:
        ts, vals, cur = ts[0], vals[0], cur[0]
    return st.RingStore(
        ts=jnp.asarray(np.ascontiguousarray(ts)),
        vals=jnp.asarray(
            np.ascontiguousarray(st.to_stored(vals, int(sharded)))
        ),
        cursor=jnp.asarray(np.ascontiguousarray(cur), jnp.int32),
    )


def _written_mask(cur: np.ndarray, C: int) -> np.ndarray:
    """(..., C) bool: ring slots that have ever been written (slot s is
    first written when the key's cursor passes s)."""
    return cur[..., None] > np.arange(C, dtype=np.int64)


def _collect_cols(e) -> List[str]:
    if isinstance(e, Col):
        return [e.name]
    out: List[str] = []
    for c in e.children():
        out.extend(_collect_cols(c))
    return out


def _synth_refusal(slot: LaneSlot, src_plan: RingPlan, ctx: str) -> Optional[str]:
    """Why ``slot`` cannot be synthesized from ``src_plan``'s stored
    lanes (None when it can)."""
    if not slot.synthesizable:
        return (
            f"lane {slot.key!r} of {ctx} contains hash/signature nodes "
            "whose evaluation is dtype-sensitive — it cannot be "
            "synthesized bit-exactly from stored f32 columns"
        )
    for name in _collect_cols(slot.expr):
        if ("col", name) not in src_plan.lane_keys:
            return (
                f"new lane {slot.key!r} of {ctx} needs raw column "
                f"{name!r}, which the running layout does not materialize "
                "(plan with raw_lanes=True to make the store evolvable)"
            )
    return None


def _synth_lane(
    slot: LaneSlot,
    src_plan: RingPlan,
    vals_src: np.ndarray,       # (..., F_src) raw lane values
    report: MigrationReport,
    ctx: str,
) -> np.ndarray:
    """Re-materialize one lane from the source ring's raw-column lanes.

    Bit-exact vs ingest-time evaluation for pure f32 row math (see
    :func:`repro.core.layout.synthesizable`); anything else requires a
    rebuild (or an offline backfill source) and fails loudly here.
    """
    why = _synth_refusal(slot, src_plan, ctx)
    if why is not None:
        raise ValueError(
            f"cannot hot-deploy: {why}; rebuild the plane for this "
            "deployment, or pass a backfill= source covering "
            f"table {ctx!r}"
        )
    with get_telemetry().tracer.span(
        "migrate.synthesize", table=ctx, lane=str(slot.key)
    ):
        cols: Dict[str, jnp.ndarray] = {}
        for name in _collect_cols(slot.expr):
            ck = ("col", name)
            cols[name] = jnp.asarray(vals_src[..., src_plan.lane_of(ck)])
        if cols:
            v = eval_rowlevel(slot.expr, cols, {}).astype(jnp.float32)
            out = np.asarray(v)
        else:  # literal-only expression
            v = eval_rowlevel(slot.expr, {}, {}).astype(jnp.float32)
            out = np.broadcast_to(np.asarray(v), vals_src.shape[:-1]).copy()
    report.synthesized_lanes.append(f"{ctx}:{slot.key!r}")
    return out


def _map_lanes(
    src_plan: RingPlan,
    dst_plan: RingPlan,
    vals_src: np.ndarray,       # (..., F_src)
    written: Optional[np.ndarray],
    report: MigrationReport,
    ctx: str,
    defer=None,                 # callable(slot, why) -> bool
) -> np.ndarray:
    """(..., F_dst) lane block: carried lanes copied by key, new lanes
    synthesized (zeroed on never-written slots, matching a fresh ring).

    ``defer`` is the backfill hook: when a new lane cannot be synthesized
    and ``defer(slot, why)`` accepts it, the lane is left zero-filled and
    recorded as a deficit for the offline splice instead of refusing.
    """
    F_dst = max(len(dst_plan.lanes), 1)
    out = np.zeros(vals_src.shape[:-1] + (F_dst,), np.float32)
    for j, slot in enumerate(dst_plan.lanes):
        if slot.key in src_plan.lane_keys:
            out[..., j] = vals_src[..., src_plan.lane_of(slot.key)]
            continue
        if defer is not None:
            why = _synth_refusal(slot, src_plan, ctx)
            if why is not None and defer(slot, why):
                continue  # zero-filled; the backfill splice overwrites
        v = _synth_lane(slot, src_plan, vals_src, report, ctx)
        out[..., j] = np.where(written, v, 0.0) if written is not None else v
    return out


def _recap(
    ts: np.ndarray,
    vals: np.ndarray,
    cur: np.ndarray,
    C_new: int,
    report: MigrationReport,
    ctx: str,
    ring_ix: Optional[int],
):
    """Re-lay ring slots for a capacity change, reproducing the cursor
    arithmetic (row at absolute index a lands in slot a % C)."""
    S, K, C_old = ts.shape
    if C_new == C_old:
        return ts, vals
    with get_telemetry().tracer.span(
        "migrate.relay", table=ctx, c_old=C_old, c_new=C_new
    ):
        r = np.minimum(cur, C_old)
        rr = np.minimum(r, C_new).astype(np.int64)
        new_ts = np.full((S, K, C_new), _TS_MIN, np.int32)
        new_vals = np.zeros((S, K, C_new, vals.shape[-1]), np.float32)
        top = int(rr.max()) if rr.size else 0
        for j in range(top):
            si, ki = np.nonzero(j < rr)
            a = cur[si, ki].astype(np.int64) - rr[si, ki] + j
            new_ts[si, ki, a % C_new] = ts[si, ki, a % C_old]
            new_vals[si, ki, a % C_new] = vals[si, ki, a % C_old]
    if C_new > C_old and np.any(cur > C_old):
        report.add_deficit(Deficit(
            target="ring", table=ctx, ring=ring_ix, lanes=None,
            reason=(
                f"{ctx}: capacity grew {C_old}->{C_new} but rows had "
                "already aged out — a cold rebuild would retain more "
                "history"
            ),
        ))
    return new_ts, new_vals


def _relane_ring(
    src_plan: RingPlan,
    dst_plan: RingPlan,
    ring: st.RingStore,
    sharded: bool,
    report: MigrationReport,
    ring_ix: Optional[int] = None,
    defer=None,
) -> st.RingStore:
    """Same key domain & placement: permute/append/synthesize lanes, then
    re-lay capacity if it changed."""
    with get_telemetry().tracer.span(
        "migrate.relane", table=dst_plan.table
    ):
        ts, vals, cur = _host_ring(ring, sharded)
        ctx = dst_plan.table
        written = _written_mask(cur, src_plan.capacity)
        vals = _map_lanes(
            src_plan, dst_plan, vals, written, report, ctx, defer=defer
        )
        ts, vals = _recap(
            ts, vals, cur, dst_plan.capacity, report, ctx, ring_ix
        )
        report.migrated.append(dst_plan.describe())
        return _mk_ring(ts, vals, cur, sharded)


def _decode_streams(
    plan: RingPlan,
    ring_h,
    store,
    report: MigrationReport,
):
    """Source ring -> {global key: (ts (r,), vals (r, F), total_rows)} —
    per-key rows oldest->newest, exactly the per-key stream suffix the
    ring retains."""
    ts, vals, cur = ring_h
    S = ts.shape[0]
    C = plan.capacity
    streams = {}
    if plan.partitioned:
        perm = store._perm
        for s in range(S):
            occupied = np.nonzero(cur[s] > 0)[0]
            if not len(occupied):
                continue
            routed = occupied.astype(np.int64) * S + s
            # algebraic Feistel inverse — O(occupied keys), not a
            # full-domain forward sweep to build a lookup table
            gids = perm.inverse(routed) if perm is not None else routed
            for l, g in zip(occupied, gids):
                c = int(cur[s, l])
                r = min(c, C)
                slots = np.arange(c - r, c, dtype=np.int64) % C
                streams[int(g)] = (ts[s, l, slots], vals[s, l, slots], c)
    else:
        # replicas are identical; decode shard 0
        occupied = np.nonzero(cur[0] > 0)[0]
        for g in occupied:
            c = int(cur[0, g])
            r = min(c, C)
            slots = np.arange(c - r, c, dtype=np.int64) % C
            streams[int(g)] = (ts[0, g, slots], vals[0, g, slots], c)
    return streams


def _reroute_ring(
    src_plan: RingPlan,
    dst_plan: RingPlan,
    ring: st.RingStore,
    store,
    sharded: bool,
    report: MigrationReport,
    ring_ix: Optional[int] = None,
    defer=None,
) -> st.RingStore:
    """Placement change (partitioned <-> replicated, e.g. building a
    dual-use table's replicated join slice from its partitioned union
    ring): decode per-key row streams, re-encode under the new plan."""
    with get_telemetry().tracer.span(
        "migrate.reroute", table=dst_plan.table,
        partitioned=dst_plan.partitioned,
    ):
        return _reroute_ring_impl(
            src_plan, dst_plan, ring, store, sharded, report, ring_ix,
            defer,
        )


def _reroute_ring_impl(
    src_plan: RingPlan,
    dst_plan: RingPlan,
    ring: st.RingStore,
    store,
    sharded: bool,
    report: MigrationReport,
    ring_ix: Optional[int] = None,
    defer=None,
) -> st.RingStore:
    S = store.num_shards if sharded else 1
    streams = _decode_streams(
        src_plan, _host_ring(ring, sharded), store, report
    )
    ctx = f"{dst_plan.table}({'part' if dst_plan.partitioned else 'repl'})"
    F_dst = max(len(dst_plan.lanes), 1)
    K_t, C_t = dst_plan.ring_keys, dst_plan.capacity
    ts_n = np.full((S, K_t, C_t), _TS_MIN, np.int32)
    vals_n = np.zeros((S, K_t, C_t, F_dst), np.float32)
    cur_n = np.zeros((S, K_t), np.int32)
    deficient = False
    for g, (ts_g, vl_g, c) in streams.items():
        if g >= dst_plan.num_keys:
            report.notes.append(
                f"{ctx}: dropped rows of out-of-domain key {g}"
            )
            report.exact = False
            report.hard_inexact = True
            continue
        rows = _map_lanes(
            src_plan, dst_plan, vl_g, None, report, ctx, defer=defer
        )
        r = len(ts_g)
        if min(c, C_t) > r and not deficient:
            deficient = True
            report.add_deficit(Deficit(
                target="ring", table=dst_plan.table, ring=ring_ix,
                lanes=None,
                reason=(
                    f"{ctx}: key {g} lost {min(c, C_t) - r} aged-out rows "
                    "vs a cold rebuild"
                ),
            ))
        rr = min(r, C_t)
        a = np.arange(c - rr, c, dtype=np.int64)
        if dst_plan.partitioned:
            s_arr, l_arr = store._route_ids(
                np.array([g], np.int64), dst_plan.num_keys
            )
            s, l = int(s_arr[0]), int(l_arr[0])
            ts_n[s, l, a % C_t] = ts_g[r - rr:]
            vals_n[s, l, a % C_t] = rows[r - rr:]
            cur_n[s, l] = c
        else:
            ts_n[:, g, a % C_t] = ts_g[r - rr:]
            vals_n[:, g, a % C_t] = rows[r - rr:]
            cur_n[:, g] = c
    report.migrated.append(dst_plan.describe())
    return _mk_ring(ts_n, vals_n, cur_n, sharded)


def _fresh_ring(plan: RingPlan, sharded: bool, S: int) -> st.RingStore:
    r = st.ring_init(plan.ring_keys, plan.capacity, max(len(plan.lanes), 1))
    if sharded:
        r = st.RingStore(
            ts=jnp.broadcast_to(r.ts, (S,) + r.ts.shape),
            vals=jnp.broadcast_to(r.vals, (S,) + r.vals.shape),
            cursor=jnp.broadcast_to(r.cursor, (S,) + r.cursor.shape),
        )
    return r


# ---------------------------------------------------------------------------
# Bucket pre-aggregate migration
# ---------------------------------------------------------------------------


_LANE_IDENT_NP = {
    "sum": np.float32(0.0),
    "count": np.float32(0.0),
    "min": np.float32(POS_INF),
    "max": np.float32(NEG_INF),
    "sumsq": np.float32(0.0),
}


def _rebuild_bucket_lane(
    v: np.ndarray,        # (S, K, C) new-lane ring values
    ts: np.ndarray,       # (S, K, C)
    cur: np.ndarray,      # (S, K)
    bucket_ids: np.ndarray,  # (S, K, NB)
    bsize: int,
):
    """Per-(key, bucket) algebra states for one lane, folded from the
    ring's retained rows oldest -> newest.

    The left-to-right f32 association matches ``bucket_ingest``'s
    scatter-add order row-for-row, so under a replay whose batches bring
    at most one row per (key, bucket) each (the live-service pattern) the
    rebuilt states are bit-identical to having ingested with the lane
    present all along.
    """
    S, K, C = v.shape
    written = _written_mask(cur, C)
    rowb = np.where(written, ts.astype(np.int64) // bsize, np.int64(-2))
    match = (rowb[:, :, None, :] == bucket_ids[..., None].astype(np.int64)) & (
        bucket_ids[..., None] >= 0
    )  # (S, K, NB, C)
    vm = np.where(match, v[:, :, None, :], np.float32(0.0)).astype(np.float32)
    s_sum = np.cumsum(vm, axis=-1, dtype=np.float32)[..., -1]
    s_cnt = match.sum(-1).astype(np.float32)
    s_min = np.where(match, v[:, :, None, :], _LANE_IDENT_NP["min"]).min(-1)
    s_max = np.where(match, v[:, :, None, :], _LANE_IDENT_NP["max"]).max(-1)
    sq = np.where(
        match, (v[:, :, None, :] * v[:, :, None, :]).astype(np.float32), 0.0
    ).astype(np.float32)
    s_sq = np.cumsum(sq, axis=-1, dtype=np.float32)[..., -1]
    by_name = {
        "sum": s_sum, "count": s_cnt, "min": s_min, "max": s_max,
        "sumsq": s_sq,
    }
    stats = np.stack([by_name[l] for l in LANES], axis=-1)
    bm_rows = np.asarray(row_bitmap(jnp.asarray(v)))  # (S, K, C) int32
    bitmap = np.bitwise_or.reduce(
        np.where(match, bm_rows[:, :, None, :], 0), axis=-1
    ).astype(np.int32)
    return stats, bitmap


_TS_EMPTY_NP = np.int32(-2147483648)


def _rebuild_bucket_order(
    vals: np.ndarray,        # (S, K, C, F) new-ring lane values
    ts: np.ndarray,          # (S, K, C)
    cur: np.ndarray,         # (S, K)
    bucket_ids: np.ndarray,  # (S, K, NB)
    bsize: int,
    want_ext: bool,
    want_tail: bool,
) -> Dict[str, np.ndarray]:
    """Merge-order families (extreme winners / newest-rows tail)
    re-derived from the ring's retained rows.

    The absolute arrival index of ring slot ``j`` is exactly
    ``cur-1-((cur-1-j) % C)`` — the newest arrival mapping to that slot —
    so the rebuilt (ts, pos) coordinates equal having persisted the
    families all along, for every row the ring still retains.
    """
    S, K, C = ts.shape
    written = _written_mask(cur, C)
    j = np.arange(C, dtype=np.int64)
    cur64 = cur[..., None].astype(np.int64)
    pos = cur64 - 1 - ((cur64 - 1 - j) % C)                  # (S, K, C)
    ts64 = ts.astype(np.int64)
    rowb = np.where(written, ts64 // bsize, np.int64(-2))
    match = (
        rowb[:, :, None, :] == bucket_ids[..., None].astype(np.int64)
    ) & (bucket_ids[..., None] >= 0)                         # (S, K, NB, C)
    tsb = np.broadcast_to(ts64[:, :, None, :], match.shape)
    posb = np.broadcast_to(pos[:, :, None, :], match.shape)
    sI = np.arange(S)[:, None, None]
    kI = np.arange(K)[None, :, None]
    out: Dict[str, np.ndarray] = {}
    if want_ext:
        has = match.any(-1)
        picks, b_ts, b_pos = [], [], []
        for newest in (False, True):
            lim = np.int64(-(2 ** 62)) if newest else np.int64(2 ** 62)
            red = np.max if newest else np.min
            bt = red(np.where(match, tsb, lim), -1)
            cand = match & (tsb == bt[..., None])
            bp = red(np.where(cand, posb, lim), -1)
            picks.append(np.argmax(cand & (posb == bp[..., None]), -1))
            b_ts.append(bt)
            b_pos.append(bp)
        h2 = np.stack([has, has], -1)
        xval = np.stack([vals[sI, kI, p] for p in picks], -1)
        out["xts"] = np.where(
            h2, np.stack(b_ts, -1), np.int64(_TS_EMPTY_NP)
        ).astype(np.int32)
        out["xpos"] = np.where(h2, np.stack(b_pos, -1), 0).astype(np.int32)
        out["xval"] = np.where(
            h2[:, :, :, None, :], xval, 0.0
        ).astype(np.float32)
        out["xhas"] = h2
    if want_tail:
        T, m = int(TOPN_TAIL), min(C, int(TOPN_TAIL))
        # descending (ts, pos): pos < 2^32, so ts*2^32+pos is the exact
        # lexicographic encoding; ascending argsort of its negation
        big = np.iinfo(np.int64).max
        inv = np.where(match, -(tsb * (2 ** 32) + posb), big)
        order = np.argsort(inv, axis=-1, kind="stable")[..., :m]
        valid = np.take_along_axis(inv, order, -1) != big
        r_ts = np.take_along_axis(tsb, order, -1)
        r_pos = np.take_along_axis(posb, order, -1)
        sI4, kI4 = sI[..., None], kI[..., None]
        r_val = np.moveaxis(vals[sI4, kI4, order], -1, -2)  # (S,K,NB,F,m)

        def pad_t(a, fill):
            if m == T:
                return a
            return np.concatenate(
                [a, np.full(a.shape[:-1] + (T - m,), fill, a.dtype)], -1
            )

        out["tts"] = pad_t(
            np.where(valid, r_ts, np.int64(_TS_EMPTY_NP)).astype(np.int32),
            _TS_EMPTY_NP,
        )
        out["tpos"] = pad_t(np.where(valid, r_pos, 0).astype(np.int32), 0)
        out["tval"] = pad_t(
            np.where(valid[:, :, :, None, :], r_val, 0.0).astype(np.float32),
            np.float32(0.0),
        )
        out["tvalid"] = pad_t(valid, False)
    return out


def _migrate_bucket(
    diff: LayoutDiff,
    bagg,
    new_ring: st.RingStore,
    sharded: bool,
    report: MigrationReport,
):
    """Carry bucket states per lane; remap slots on num_buckets changes;
    re-fold new lanes from the (already migrated) primary ring."""
    from repro.core import preagg as pg

    src_p, dst_p = diff.old.primary, diff.new.primary
    NB_o, NB_n = diff.old.bucket.num_buckets, diff.new.bucket.num_buckets
    bsize = diff.new.bucket.bucket_size

    host = pg.bucket_to_host(bagg, int(sharded))
    stats, bitmap, bucket = host["stats"], host["bitmap"], host["bucket"]
    if not sharded:
        stats, bitmap, bucket = stats[None], bitmap[None], bucket[None]

    # merge-order families the NEW plan persists; carry the old arrays
    # when the old store has them (same remap as stats below)
    want_ext = getattr(diff.new.bucket, "extreme", False)
    want_tail = getattr(diff.new.bucket, "tail", False)
    fam: Dict[str, np.ndarray] = {}
    fam_src = (want_ext or want_tail) and (
        (not want_ext or bagg.xts is not None)
        and (not want_tail or bagg.tts is not None)
    )
    if fam_src:
        names = (("xts", "xpos", "xval", "xhas") if want_ext else ()) + (
            ("tts", "tpos", "tval", "tvalid") if want_tail else ()
        )
        for nm in names:
            fam[nm] = host[nm] if sharded else host[nm][None]

    if NB_n != NB_o:
        if np.any(bucket >= NB_o):
            # some slot has cycled at least once -> older buckets of the
            # finer/coarser new ring may be unrecoverable
            report.add_deficit(Deficit(
                target="bucket", table=dst_p.table, lanes=None,
                reason=(
                    f"primary: num_buckets {NB_o}->{NB_n} after "
                    "bucket-ring wraparound — a cold rebuild would retain "
                    "different buckets"
                ),
            ))
        order = np.argsort(bucket, axis=-1, kind="stable")
        b_s = np.take_along_axis(bucket, order, -1)
        st_s = np.take_along_axis(stats, order[..., None, None], 2)
        bm_s = np.take_along_axis(bitmap, order[..., None], 2)
        tgt = np.where(b_s >= 0, b_s % NB_n, NB_n)  # invalid -> spill slot
        S, K = bucket.shape[:2]
        F_o, NS = stats.shape[-2], stats.shape[-1]
        bucket_n = np.full((S, K, NB_n + 1), -1, np.int32)
        stats_n = np.broadcast_to(
            np.array([_LANE_IDENT_NP[l] for l in LANES], np.float32),
            (S, K, NB_n + 1, F_o, NS),
        ).copy()
        bitmap_n = np.zeros((S, K, NB_n + 1, F_o), np.int32)
        # ascending bucket ids: later (larger) ids win slot conflicts,
        # matching the ring's newest-bucket-per-slot retention
        np.put_along_axis(bucket_n, tgt, b_s, axis=2)
        np.put_along_axis(stats_n, tgt[..., None, None], st_s, axis=2)
        np.put_along_axis(bitmap_n, tgt[..., None], bm_s, axis=2)
        bucket, stats, bitmap = (
            bucket_n[..., :NB_n],
            stats_n[..., :NB_n, :, :],
            bitmap_n[..., :NB_n, :],
        )
        fam_empty = {
            "xts": (_TS_EMPTY_NP, 1), "xpos": (np.int32(0), 1),
            "xval": (np.float32(0.0), 2), "xhas": (False, 1),
            "tts": (_TS_EMPTY_NP, 1), "tpos": (np.int32(0), 1),
            "tval": (np.float32(0.0), 2), "tvalid": (False, 1),
        }
        for nm, a in fam.items():
            empty, extra = fam_empty[nm]
            idx = order.reshape(order.shape + (1,) * extra)
            a_s = np.take_along_axis(a, idx, 2)
            a_n = np.full((S, K, NB_n + 1) + a.shape[3:], empty, a.dtype)
            np.put_along_axis(
                a_n, tgt.reshape(tgt.shape + (1,) * extra), a_s, 2
            )
            fam[nm] = a_n[:, :, :NB_n]

    # lane remap / rebuild
    ts_h, vals_h, cur_h = _host_ring(new_ring, sharded)
    F_n = max(len(dst_p.lanes), 1)
    S, K = bucket.shape[:2]
    NS = stats.shape[-1]
    stats_out = np.broadcast_to(
        np.array([_LANE_IDENT_NP[l] for l in LANES], np.float32),
        (S, K, NB_n, F_n, NS),
    ).copy()
    bitmap_out = np.zeros((S, K, NB_n, F_n), np.int32)
    # the rebuild folds the (already re-capped) NEW ring, so rows beyond
    # EITHER capacity are gone — a cold rebuild's bucket store saw them
    ring_lost = bool(
        np.any(cur_h > min(src_p.capacity, dst_p.capacity))
    )
    # primary-ring lanes the migration zero-filled for the backfill
    # splice: their ring values are NOT usable as a fold source
    deferred = {
        k
        for d in report.deficits
        if d.target == "ring" and d.ring is None and d.lanes
        for k in d.lanes
    }
    for j, slot in enumerate(dst_p.lanes):
        if slot.key in src_p.lane_keys:
            i = src_p.lane_of(slot.key)
            stats_out[..., j, :] = stats[..., i, :]
            bitmap_out[..., j] = bitmap[..., i]
        elif slot.key in deferred:
            # identities stay in place; the splice re-folds from history
            report.add_deficit(Deficit(
                target="bucket", table=dst_p.table, lanes=(slot.key,),
                reason=(
                    f"primary: bucket states for deferred lane "
                    f"{slot.key!r} await the backfill splice"
                ),
            ))
        else:
            st_j, bm_j = _rebuild_bucket_lane(
                vals_h[..., j], ts_h, cur_h, bucket, bsize
            )
            stats_out[..., j, :] = st_j
            bitmap_out[..., j] = bm_j
            if ring_lost:
                report.add_deficit(Deficit(
                    target="bucket", table=dst_p.table, lanes=(slot.key,),
                    reason=(
                        f"primary: bucket states for new lane {slot.key!r} "
                        "rebuilt from ring-retained rows only (older rows "
                        "had aged out)"
                    ),
                ))
    # merge-order family outputs: carry (lane-gathered) when every dst
    # lane exists in the source arrays, else re-derive from the new ring
    fam_kw: Dict[str, np.ndarray] = {}
    if want_ext or want_tail:
        # per-key arrival counter ≡ ring cursor (both count every arrival)
        fam_kw["seq"] = cur_h.astype(np.int32)
        lanes_ok = bool(dst_p.lanes) and all(
            s.key in src_p.lane_keys for s in dst_p.lanes
        )
        if fam_src and lanes_ok:
            li = [src_p.lane_of(s.key) for s in dst_p.lanes]
            if want_ext:
                fam_kw["xts"], fam_kw["xpos"] = fam["xts"], fam["xpos"]
                fam_kw["xhas"] = fam["xhas"]
                fam_kw["xval"] = fam["xval"][..., li, :]
            if want_tail:
                fam_kw["tts"], fam_kw["tpos"] = fam["tts"], fam["tpos"]
                fam_kw["tvalid"] = fam["tvalid"]
                fam_kw["tval"] = fam["tval"][..., li, :]
        else:
            fam_kw.update(_rebuild_bucket_order(
                vals_h, ts_h, cur_h, bucket, bsize, want_ext, want_tail
            ))
            if ring_lost:
                report.add_deficit(Deficit(
                    target="bucket", table=dst_p.table, lanes=None,
                    reason=(
                        "primary: merge-order bucket states (extreme/tail)"
                        " rebuilt from ring-retained rows only (older rows"
                        " had aged out)"
                    ),
                ))
    if not sharded:
        stats_out, bitmap_out, bucket = (
            stats_out[0], bitmap_out[0], bucket[0]
        )
        fam_kw = {k: v[0] for k, v in fam_kw.items()}
    report.migrated.append(
        f"bucket[{NB_o}->{NB_n} x {bsize}, lanes {stats.shape[-2]}->{F_n}]"
    )
    return pg.bucket_from_host(
        bsize,
        dict(stats=stats_out, bitmap=bitmap_out, bucket=bucket, **fam_kw),
        int(sharded),
    )


# ---------------------------------------------------------------------------
# The migration
# ---------------------------------------------------------------------------


def _make_deferrer(backfill, plan: RingPlan, ring_ix, report):
    """Build the per-ring lane-deferral hook: a new lane that cannot be
    synthesized is zero-filled and recorded as a deficit — but only when
    the backfill source actually holds the table's history columns, so a
    migration never silently defers into an unservable splice."""
    if backfill is None:
        return None

    def defer(slot: LaneSlot, why: str) -> bool:
        if not backfill.covers(plan.table, slot.expr):
            return False
        report.add_deficit(Deficit(
            target="ring", table=plan.table, ring=ring_ix,
            lanes=(slot.key,),
            reason=f"{why} — deferred to the offline backfill splice",
        ))
        return True

    return defer


def migrate_state(
    diff: LayoutDiff,
    old_state: OnlineState,
    store,  # OnlineFeatureStore already switched to diff.new
    backfill=None,  # repro.offline.backfill.BackfillSource (duck-typed)
) -> Tuple[OnlineState, MigrationReport]:
    """Transform ``old_state`` (laid out per ``diff.old``) into a state
    laid out per ``diff.new``.  Returns host-or-device arrays; the caller
    places them (:meth:`OnlineFeatureStore._place_state`).

    ``backfill`` only changes *refusal* behaviour here: lanes that cannot
    be synthesized from stored columns are deferred (zero-filled +
    recorded in ``report.deficits``) when the source covers their table.
    The actual splice happens in the caller, against the full report.
    """
    sharded = diff.new.num_shards is not None
    S = diff.new.num_shards or 1
    report = MigrationReport(diff_summary=diff.summary())
    tracer = get_telemetry().tracer

    with tracer.span("migrate", tables=len(diff.new.tables)):
        # -- primary ring + bucket store -----------------------------------
        if diff.primary_carried:
            with tracer.span(
                "migrate.carry", table=diff.new.primary.table
            ):
                ring = old_state.ring
            report.carried.append(diff.new.primary.describe())
        else:
            ring = _relane_ring(
                diff.old.primary, diff.new.primary, old_state.ring,
                sharded, report, ring_ix=None,
                defer=_make_deferrer(
                    backfill, diff.new.primary, None, report
                ),
            )
        if diff.bucket_carried:
            with tracer.span("migrate.carry", table="bucket"):
                bagg = old_state.bagg
            report.carried.append(
                f"bucket[{diff.new.bucket.num_buckets} x "
                f"{diff.new.bucket.bucket_size}]"
            )
        else:
            with tracer.span("migrate.bucket", table=diff.new.primary.table):
                bagg = _migrate_bucket(
                    diff, old_state.bagg, ring, sharded, report
                )

        # -- secondary rings ------------------------------------------------
        sec: List[st.RingStore] = []
        for i, plan in enumerate(diff.new.tables):
            src = diff.ring_sources[i]
            if src is None:
                with tracer.span("migrate.fresh", table=plan.table):
                    sec.append(_fresh_ring(plan, sharded, S))
                report.fresh.append(plan.describe())
                continue
            src_plan = diff.old.tables[src]
            if diff.carried[i]:
                with tracer.span("migrate.carry", table=plan.table):
                    sec.append(old_state.sec[src])
                report.carried.append(plan.describe())
            elif (
                src_plan.partitioned == plan.partitioned
                and src_plan.ring_keys == plan.ring_keys
            ):
                sec.append(
                    _relane_ring(
                        src_plan, plan, old_state.sec[src], sharded,
                        report, ring_ix=i,
                        defer=_make_deferrer(backfill, plan, i, report),
                    )
                )
            else:
                sec.append(
                    _reroute_ring(
                        src_plan, plan, old_state.sec[src], store, sharded,
                        report, ring_ix=i,
                        defer=_make_deferrer(backfill, plan, i, report),
                    )
                )
        for i in diff.dropped:
            report.dropped.append(diff.old.tables[i].describe())

    return (
        OnlineState(ring=ring, bagg=bagg, sec=tuple(sec)),
        report,
    )
