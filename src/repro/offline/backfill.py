"""Offline backfill bridge — exact migrations beyond the retention horizon.

The online plane's rings retain only the last ``capacity`` rows per key,
so a hot deployment that needs older state (a capacity grow after rows
aged out, a placement change over a wrapped ring, a lane that cannot be
synthesized from stored f32 columns) either refuses or completes with
``report.exact = False``.  FeatInsight's answer is its offline half: the
full history lives in offline storage, and feature state is *re-derived*
from it with the same computation that ran online.

:class:`BackfillSource` is that bridge for the JAX stores.  Given
per-table raw-column history (exactly the column batches that were
ingested online, any order), it

* re-derives **ring state**: lane values via the same
  :func:`~repro.core.expr.eval_rowlevel` f32 evaluation ingest uses
  (elementwise, so bit-exact row-for-row — including hash/signature
  lanes the lane-synthesis path must refuse), laid out with the ring's
  own cursor arithmetic (row at absolute index ``a`` lands in slot
  ``a % C``) and the store's own shard routing;
* re-derives **bucket pre-aggregate state**: per-(key, bucket) algebra
  folds in the canonical ``lexsort((ts, key))`` stream order with
  unbuffered left-to-right f32 accumulation — the association
  ``bucket_ingest`` applies — over *all* history rows, not just the
  ring-retained suffix;
* **splices** the re-derived state over every structured
  :class:`~repro.core.migrate.Deficit` a migration recorded, restoring
  ``report.exact`` (hot == cold rebuild + full replay, bit-for-bit).

Safety contract: the splice runs *before* the new layout goes live
(:meth:`~repro.core.online.OnlineFeatureStore.adopt_layout`), and it
verifies the re-derived per-key row counts against the live store's
cursors — a history that does not reproduce the online stream raises
loudly and leaves the plane serving the old layout, exactly like a
refused migration.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import preagg as pg
from repro.core import storage as st
from repro.core.aggregates import (
    LANES,
    NEG_INF,
    POS_INF,
    TOPN_TAIL,
    row_bitmap,
)
from repro.core.expr import (
    collect_last_joins,
    collect_window_aggs,
    eval_rowlevel,
)
from repro.core.layout import LayoutDiff, RingPlan
from repro.core.migrate import MigrationReport, _collect_cols, _mk_ring
from repro.core.online import OnlineState
from repro.obs import get_telemetry

__all__ = ["BackfillAction", "BackfillPlan", "BackfillSource"]

_TS_MIN = np.int32(-2147483648)

_IDENT = {
    "sum": np.float32(0.0),
    "count": np.float32(0.0),
    "min": np.float32(POS_INF),
    "max": np.float32(NEG_INF),
    "sumsq": np.float32(0.0),
}


@dataclasses.dataclass(frozen=True)
class BackfillAction:
    """One state re-derivation the splice will perform (or refuse).

    Mirrors the :class:`~repro.core.migrate.Deficit` it repairs, plus the
    offline side of the ledger: how many history rows the source holds
    for the table (``rows``; per-shard breakdown for partitioned rings)
    and whether the source actually covers the re-derivation
    (``covered`` — table present, every needed raw column present, keys
    inside the plan's domain).
    """

    target: str                       # 'ring' | 'bucket'
    table: str
    ring: Optional[int] = None        # new.tables index; None = primary
    lanes: Optional[Tuple] = None
    rows: int = 0
    rows_per_shard: Tuple[int, ...] = ()
    covered: bool = True
    reason: str = ""

    def describe(self) -> str:
        what = (
            "all lanes" if self.lanes is None
            else ", ".join(repr(k) for k in self.lanes)
        )
        tag = "" if self.covered else f"  UNCOVERED: {self.reason}"
        return (
            f"{self.target} {self.table} [{what}] "
            f"<- {self.rows} history rows{tag}"
        )


@dataclasses.dataclass
class BackfillPlan:
    """What a backfill splice will do for one migration's deficits."""

    actions: List[BackfillAction] = dataclasses.field(default_factory=list)

    @property
    def covered(self) -> bool:
        return all(a.covered for a in self.actions)

    @property
    def total_rows(self) -> int:
        return sum(a.rows for a in self.actions)

    def describe(self) -> str:
        if not self.actions:
            return "backfill plan: nothing to re-derive"
        lines = [
            f"backfill plan: {len(self.actions)} action(s), "
            f"{self.total_rows} history rows, "
            f"covered={'yes' if self.covered else 'NO'}"
        ]
        for a in self.actions:
            lines.append(f"  {a.describe()}")
        return "\n".join(lines)


def _features_needing(view, table: str, lanes: Optional[Tuple]) -> List[str]:
    """Best-effort: which view features depend on the deficient state
    (for refusal messages that name the offender)."""
    names: List[str] = []
    for fname, expr in view.features.items():
        waggs = collect_window_aggs([expr])
        ljs = collect_last_joins([expr])
        if lanes:
            if any(wa.arg.key in lanes for wa in waggs.values()) or any(
                lj.arg.key in lanes for lj in ljs.values()
            ):
                names.append(fname)
            continue
        if any(table in wa.union for wa in waggs.values()) or any(
            lj.table == table for lj in ljs.values()
        ):
            names.append(fname)
    if not names and lanes is None:
        # primary-table deficits touch every windowed feature
        names = [
            f for f, e in view.features.items() if collect_window_aggs([e])
        ]
    return names


class BackfillSource:
    """Per-table raw-column history, servable into a migrating plane.

    ``tables`` maps table name -> column dict (including the schema's key
    and ts columns), holding the *complete* stream that was ingested
    online — same values, same dtypes, in ingest order (ties in
    ``(key, ts)`` keep their original relative order, matching the
    store's stable batch sorts).  Feed it to
    ``MultiScenarioService.hot_deploy(view, backfill=source)`` /
    ``ScenarioPlane.evolve`` / ``OnlineFeatureStore.adopt_layout``; the
    export side (:mod:`repro.offline.export`) reads the same object.
    """

    def __init__(self, database, tables: Dict[str, Dict[str, np.ndarray]]):
        self.database = database
        self.tables: Dict[str, Dict[str, np.ndarray]] = {}
        for name, cols in tables.items():
            sch = database.table(name)  # raises on unknown tables
            missing = [c for c in (sch.key, sch.ts) if c not in cols]
            if missing:
                raise ValueError(
                    f"backfill history for table {name!r} lacks required "
                    f"column(s) {missing} (schema key={sch.key!r}, "
                    f"ts={sch.ts!r})"
                )
            arrs = {c: np.asarray(v) for c, v in cols.items()}
            sizes = {c: a.shape[0] for c, a in arrs.items()}
            if len(set(sizes.values())) > 1:
                raise ValueError(
                    f"backfill history for table {name!r} has ragged "
                    f"columns: {sizes}"
                )
            self.tables[name] = arrs
        self._streams: Dict[str, Tuple] = {}

    # -- history access -----------------------------------------------------

    def rows(self, table: str) -> int:
        return (
            0 if table not in self.tables
            else next(iter(self.tables[table].values())).shape[0]
        )

    def stream(self, table: str):
        """Canonical history stream of ``table``:
        ``(key (N,) i64, ts (N,) i32, columns {name: (N,)})`` sorted by
        the store's canonical ``lexsort((ts, key))`` order — the order
        every exact fold below replays."""
        if table in self._streams:
            return self._streams[table]
        sch = self.database.table(table)
        cols = self.tables[table]
        key = np.asarray(cols[sch.key]).astype(np.int64)
        ts = np.asarray(cols[sch.ts]).astype(np.int32)
        order = np.lexsort((ts, key))
        out = (
            key[order],
            ts[order],
            {c: np.asarray(v)[order] for c, v in cols.items()},
        )
        self._streams[table] = out
        return out

    # -- coverage -----------------------------------------------------------

    def covers(self, table: str, expr) -> bool:
        """Can ``expr``'s lane be re-derived for ``table`` from this
        history?  (The migration's deferral hook — a lane is only
        zero-filled for the splice when this says yes.)"""
        if table not in self.tables:
            return False
        cols = self.tables[table]
        return all(c in cols for c in _collect_cols(expr))

    def _plan_coverage(
        self, plan: RingPlan, lanes: Optional[Tuple]
    ) -> Optional[str]:
        """None when every needed lane of ``plan`` is derivable from the
        history; otherwise why not."""
        if plan.table not in self.tables:
            return (
                f"backfill source holds no history for table "
                f"{plan.table!r} (has {sorted(self.tables)})"
            )
        cols = self.tables[plan.table]
        need = (
            plan.lanes if lanes is None
            else [s for s in plan.lanes if s.key in lanes]
        )
        for slot in need:
            missing = [c for c in _collect_cols(slot.expr) if c not in cols]
            if missing:
                return (
                    f"lane {slot.key!r} of table {plan.table!r} needs raw "
                    f"column(s) {missing} absent from the backfill history"
                )
        return None

    # -- planning -----------------------------------------------------------

    def plan(
        self, diff: LayoutDiff, report: MigrationReport, store
    ) -> BackfillPlan:
        """Resolve a migration's deficits against this history: one
        :class:`BackfillAction` per deficit, with coverage verdicts and
        per-(table, shard) history row counts.  Pure introspection — no
        state is touched (``splice`` executes covered plans)."""
        sharded = diff.new.num_shards is not None
        S = diff.new.num_shards or 1
        out = BackfillPlan()
        for d in report.deficits:
            ring_plan = (
                diff.new.primary if d.target == "bucket" or d.ring is None
                else diff.new.tables[d.ring]
            )
            # a ring deficit rebuilds the WHOLE ring (every lane needs its
            # raw columns); a per-lane bucket re-fold needs only its own
            need_lanes = d.lanes if d.target == "bucket" else None
            why = self._plan_coverage(ring_plan, need_lanes)
            rows = self.rows(ring_plan.table)
            per_shard: Tuple[int, ...] = ()
            if why is None and rows:
                key, _, _ = self.stream(ring_plan.table)
                if ring_plan.partitioned and sharded:
                    try:
                        s_ids, _ = store._route_ids(key, ring_plan.num_keys)
                        per_shard = tuple(
                            np.bincount(s_ids, minlength=S).tolist()
                        )
                    except ValueError as e:
                        why = str(e)
                elif key.size and (
                    key.min() < 0 or key.max() >= ring_plan.num_keys
                ):
                    why = (
                        f"history keys of table {ring_plan.table!r} fall "
                        f"outside [0, {ring_plan.num_keys}) "
                        f"(seen [{key.min()}, {key.max()}])"
                    )
                else:
                    per_shard = (rows,) * S
            out.actions.append(BackfillAction(
                target=d.target,
                table=ring_plan.table,
                ring=d.ring,
                lanes=d.lanes,
                rows=rows,
                rows_per_shard=per_shard,
                covered=why is None,
                reason=why or "",
            ))
        return out

    # -- re-derivation ------------------------------------------------------

    def _lane_values(
        self,
        plan: RingPlan,
        columns: Dict,
        lane_js: Optional[List[int]] = None,
    ) -> np.ndarray:
        """(N, max(F, 1)) f32 lane block over the history stream — the
        exact values ingest computed: elementwise
        ``eval_rowlevel(expr, raw_columns)`` over same-dtype inputs, so
        hash/signature lanes reproduce bit-for-bit.  ``lane_js`` restricts
        evaluation to those lane indices (others stay zero), so a
        per-lane bucket re-fold only needs *its* raw columns."""
        n = next(iter(columns.values())).shape[0] if columns else 0
        out = np.zeros((n, max(len(plan.lanes), 1)), np.float32)
        if not plan.lanes:
            return out
        jcols = {c: jnp.asarray(v) for c, v in columns.items()}
        js = range(len(plan.lanes)) if lane_js is None else lane_js
        for j in js:
            out[:, j] = np.asarray(
                eval_rowlevel(plan.lanes[j].expr, jcols, {}).astype(
                    jnp.float32
                )
            )
        return out

    def _routed(self, plan: RingPlan, key: np.ndarray, store, sharded: bool):
        """(shard (N,), local-row (N,)) placement of history keys under
        the store's own routing (range-checked: out-of-domain history
        keys raise, they can never be spliced silently)."""
        if plan.partitioned and sharded:
            return store._route_ids(key, plan.num_keys)
        if key.size and (key.min() < 0 or key.max() >= plan.num_keys):
            raise ValueError(
                f"history keys of table {plan.table!r} fall outside "
                f"[0, {plan.num_keys}) (seen [{key.min()}, {key.max()}])"
            )
        return np.zeros(key.shape, np.int64), key

    def _derive_ring(self, plan: RingPlan, store, sharded: bool, S: int):
        """Re-derive one ring wholesale from history: returns
        ``(ts (S,K,C), vals (S,K,C,F), cur (S,K))`` — byte-identical to a
        ring that ingested the full stream at this plan all along."""
        key, ts, cols = self.stream(plan.table)
        lanes = self._lane_values(plan, cols)
        K_t, C = plan.ring_keys, plan.capacity
        F = max(len(plan.lanes), 1)
        ts_n = np.full((S, K_t, C), _TS_MIN, np.int32)
        vals_n = np.zeros((S, K_t, C, F), np.float32)
        cur_n = np.zeros((S, K_t), np.int32)
        s_all, l_all = self._routed(plan, key, store, sharded)
        part = plan.partitioned and sharded
        for g in np.unique(key):
            idx = np.nonzero(key == g)[0]  # canonical order preserved
            c = len(idx)
            r = min(c, C)
            tail = idx[c - r:]
            slots = np.arange(c - r, c, dtype=np.int64) % C
            if part:
                s, l = int(s_all[idx[0]]), int(l_all[idx[0]])
                ts_n[s, l, slots] = ts[tail]
                vals_n[s, l, slots] = lanes[tail]
                cur_n[s, l] = c
            else:
                l = int(l_all[idx[0]])
                ts_n[:, l, slots] = ts[tail]
                vals_n[:, l, slots] = lanes[tail]
                cur_n[:, l] = c
        return ts_n, vals_n, cur_n

    def _verify_cursors(
        self, plan: RingPlan, cur_new: np.ndarray, cur_live: np.ndarray
    ) -> None:
        """The exactness tripwire: re-derived per-key row counts must
        equal the live (migrated) cursors — the store's rows-ever ledger.
        Anything else means the history is not the online stream."""
        if np.array_equal(cur_new, cur_live):
            return
        bad = int((cur_new != cur_live).sum())
        s, k = np.argwhere(cur_new != cur_live)[0]
        raise ValueError(
            f"backfill history for table {plan.table!r} does not reproduce "
            f"the online stream: per-key row counts disagree with the live "
            f"store's cursors at {bad} ring row(s) (e.g. shard {int(s)} "
            f"row {int(k)}: history has {int(cur_new[s, k])} rows, the "
            f"store ingested {int(cur_live[s, k])}); the splice needs "
            f"exactly the rows that were ingested online — rebuild the "
            f"plane or fix the history"
        )

    def _derive_bucket(
        self,
        diff: LayoutDiff,
        bagg,
        store,
        sharded: bool,
        S: int,
        full: bool,
        lane_keys: List[Tuple],
    ):
        """Re-fold bucket pre-aggregate states from the full primary
        history (``full`` rebuilds ids + every lane after a
        ``num_buckets`` wraparound; otherwise only ``lane_keys`` re-fold
        over the migrated — exact — bucket ids).

        Unbuffered ``np.*.at`` folds apply per cell in stream order, so
        the f32 association matches ``bucket_ingest`` left-to-right —
        the same argument :func:`repro.core.migrate._rebuild_bucket_lane`
        relies on, extended over the whole history instead of the ring's
        retained suffix.
        """
        dst_p = diff.new.primary
        NB = diff.new.bucket.num_buckets
        bsize = diff.new.bucket.bucket_size
        key, ts, cols = self.stream(dst_p.table)
        if full:
            lane_js = list(range(len(dst_p.lanes))) or [0]
        else:
            lane_js = [dst_p.lane_of(k) for k in lane_keys]
        # merge-order families rebuild whole-array (winner rows are
        # lane-shared), so their value gathers need every lane evaluated
        want_ext = getattr(diff.new.bucket, "extreme", False)
        want_tail = getattr(diff.new.bucket, "tail", False)
        eval_js = (
            (list(range(len(dst_p.lanes))) or [0])
            if (want_ext or want_tail)
            else lane_js
        )
        lanes = self._lane_values(
            dst_p, cols, lane_js=[j for j in eval_js if dst_p.lanes]
        )
        K = dst_p.ring_keys

        host = pg.bucket_to_host(bagg, int(sharded))
        stats = np.array(host["stats"], np.float32, copy=True)
        bitmap = np.array(host["bitmap"], np.int32, copy=True)
        bucket = np.array(host["bucket"], np.int64, copy=True)
        if not sharded:
            stats, bitmap, bucket = stats[None], bitmap[None], bucket[None]

        s_all, l_all = self._routed(dst_p, key, store, sharded)
        s_all = np.asarray(s_all, np.int64)
        l_all = np.asarray(l_all, np.int64)
        b_all = ts.astype(np.int64) // bsize
        slot_all = b_all % NB

        if full:
            # stored id per slot = max bucket id ever written (the live
            # ring's newest-bucket-wins retention)
            bucket = np.full((S, K, NB), -1, np.int64)
            np.maximum.at(bucket, (s_all, l_all, slot_all), b_all)

        # rows of each slot's *surviving* bucket (earlier buckets in the
        # same slot were reset away by the newest id)
        live = bucket[s_all, l_all, slot_all] == b_all
        si, li, bi = s_all[live], l_all[live], slot_all[live]
        for j in lane_js:
            v = lanes[live][:, j].astype(np.float32)
            acc = {
                "sum": np.zeros((S, K, NB), np.float32),
                "count": np.zeros((S, K, NB), np.float32),
                "min": np.full((S, K, NB), _IDENT["min"], np.float32),
                "max": np.full((S, K, NB), _IDENT["max"], np.float32),
                "sumsq": np.zeros((S, K, NB), np.float32),
            }
            np.add.at(acc["sum"], (si, li, bi), v)
            np.add.at(acc["count"], (si, li, bi), np.float32(1.0))
            np.minimum.at(acc["min"], (si, li, bi), v)
            np.maximum.at(acc["max"], (si, li, bi), v)
            np.add.at(acc["sumsq"], (si, li, bi), v * v)
            stats[..., j, :] = np.stack([acc[l] for l in LANES], axis=-1)
            bm = np.zeros((S, K, NB), np.int32)
            np.bitwise_or.at(
                bm, (si, li, bi),
                np.asarray(row_bitmap(jnp.asarray(v)), np.int32),
            )
            bitmap[..., j] = bm
        # merge-order families, rebuilt exactly from the full history:
        # pos is the per-(shard, local-key) cumcount in canonical stream
        # order — the same arrival-order identification _derive_ring's
        # exact replay relies on
        fam_kw: Dict[str, np.ndarray] = {}
        if want_ext or want_tail:
            F = max(len(dst_p.lanes), 1)
            n_rows = int(ts.shape[0])
            gkey = s_all * np.int64(K) + l_all
            o_g = np.argsort(gkey, kind="stable")
            go = gkey[o_g]
            startg = np.ones(n_rows, bool)
            startg[1:] = go[1:] != go[:-1]
            gid = np.cumsum(startg) - 1
            firstg = np.nonzero(startg)[0]
            pos = np.empty(n_rows, np.int64)
            pos[o_g] = np.arange(n_rows) - (
                firstg[gid] if n_rows else np.zeros(0, np.int64)
            )
            seq = np.zeros((S, K), np.int64)
            np.add.at(seq, (s_all, l_all), 1)
            fam_kw["seq"] = seq.astype(np.int32)
            comb = ts.astype(np.int64) * (2 ** 32) + pos
            si_a, li_a, bi_a = s_all[live], l_all[live], slot_all[live]
            comb_l, pos_l, ts_l = comb[live], pos[live], ts[live]
            vals_l = lanes[live].astype(np.float32)  # (M, F)
            big = np.int64(2 ** 62)
        if want_ext:
            xts = np.full((S, K, NB, 2), _TS_MIN, np.int32)
            xpos = np.zeros((S, K, NB, 2), np.int32)
            xval = np.zeros((S, K, NB, F, 2), np.float32)
            xhas = np.zeros((S, K, NB, 2), bool)
            for d, (red, lim) in enumerate(
                ((np.minimum, big), (np.maximum, -big))
            ):
                w = np.full((S, K, NB), lim, np.int64)
                red.at(w, (si_a, li_a, bi_a), comb_l)
                hit = comb_l == w[si_a, li_a, bi_a]
                sh, lh, bh = si_a[hit], li_a[hit], bi_a[hit]
                xts[sh, lh, bh, d] = ts_l[hit]
                xpos[sh, lh, bh, d] = pos_l[hit]
                xval[sh, lh, bh, :, d] = vals_l[hit]
                xhas[sh, lh, bh, d] = True
            fam_kw.update(xts=xts, xpos=xpos, xval=xval, xhas=xhas)
        if want_tail:
            T = int(TOPN_TAIL)
            tts = np.full((S, K, NB, T), _TS_MIN, np.int32)
            tpos = np.zeros((S, K, NB, T), np.int32)
            tval = np.zeros((S, K, NB, F, T), np.float32)
            tvalid = np.zeros((S, K, NB, T), bool)
            cell = (si_a * np.int64(K) + li_a) * np.int64(NB) + bi_a
            o_t = np.lexsort((-comb_l, cell))  # per cell, newest first
            co = cell[o_t]
            startc = np.ones(co.size, bool)
            startc[1:] = co[1:] != co[:-1]
            cid = np.cumsum(startc) - 1
            firstc = np.nonzero(startc)[0]
            rank = np.arange(co.size) - (
                firstc[cid] if co.size else np.zeros(0, np.int64)
            )
            keep = rank < T
            rows_k, rk = o_t[keep], rank[keep]
            sk, lk, bk = si_a[rows_k], li_a[rows_k], bi_a[rows_k]
            tts[sk, lk, bk, rk] = ts_l[rows_k]
            tpos[sk, lk, bk, rk] = pos_l[rows_k]
            tval[sk, lk, bk, :, rk] = vals_l[rows_k]
            tvalid[sk, lk, bk, rk] = True
            fam_kw.update(tts=tts, tpos=tpos, tval=tval, tvalid=tvalid)
        bucket32 = bucket.astype(np.int32)
        if not sharded:
            stats, bitmap, bucket32 = stats[0], bitmap[0], bucket32[0]
            fam_kw = {k: v[0] for k, v in fam_kw.items()}
        return pg.bucket_from_host(
            bsize,
            dict(stats=stats, bitmap=bitmap, bucket=bucket32, **fam_kw),
            int(sharded),
        )

    # -- the splice ---------------------------------------------------------

    def splice(
        self,
        diff: LayoutDiff,
        state: OnlineState,
        report: MigrationReport,
        store,
        view,
    ) -> OnlineState:
        """Repair every deficit of a migrated state from offline history.

        Runs against the *untouched* store (before the new layout goes
        live); raises — refusing the whole deployment atomically — when
        any deficit is uncoverable or the history fails the cursor
        tripwire.  On success every deficit moves to
        ``report.backfilled`` and ``report.exact`` is restored (unless
        the migration was hard-inexact, e.g. a key-domain shrink dropped
        rows no history can resurrect).
        """
        tel = get_telemetry()
        tracer = tel.tracer
        rows_ctr = tel.metrics.counter(
            "backfill_rows_total",
            "offline history rows folded by backfill splices", "1",
            labels=("table",),
        )
        sharded = diff.new.num_shards is not None
        S = diff.new.num_shards or 1

        bplan = self.plan(diff, report, store)
        for a in bplan.actions:
            if a.covered:
                continue
            feats = _features_needing(view, a.table, a.lanes)
            named = (
                f" (feature(s) {feats})" if feats else ""
            )
            raise ValueError(
                f"cannot backfill view {view.name!r}{named}: {a.reason}; "
                "extend the backfill source's history or rebuild the "
                "plane for this deployment"
            )

        with tracer.span(
            "backfill", actions=len(bplan.actions), rows=bplan.total_rows
        ):
            ring, bagg, sec = state.ring, state.bagg, list(state.sec)
            ring_targets = sorted(
                {d.ring for d in report.deficits if d.target == "ring"},
                key=lambda r: (-1 if r is None else r),
            )
            for rix in ring_targets:
                plan = (
                    diff.new.primary if rix is None else diff.new.tables[rix]
                )
                live = state.ring if rix is None else state.sec[rix]
                with tracer.span(
                    "backfill.ring", table=plan.table,
                    rows=self.rows(plan.table),
                ):
                    ts_n, vals_n, cur_n = self._derive_ring(
                        plan, store, sharded, S
                    )
                    cur_live = np.asarray(live.cursor)
                    if not sharded:
                        cur_live = cur_live[None]
                    self._verify_cursors(plan, cur_n, cur_live)
                    rebuilt = _mk_ring(ts_n, vals_n, cur_n, sharded)
                    if rix is None:
                        ring = rebuilt
                    else:
                        sec[rix] = rebuilt
                rows_ctr.inc(self.rows(plan.table), table=plan.table)

            bdefs = [d for d in report.deficits if d.target == "bucket"]
            if bdefs:
                full = any(d.lanes is None for d in bdefs)
                lane_keys = [k for d in bdefs if d.lanes for k in d.lanes]
                with tracer.span(
                    "backfill.bucket", table=diff.new.primary.table,
                    full=full, lanes=len(lane_keys),
                ):
                    bagg = self._derive_bucket(
                        diff, bagg, store, sharded, S, full, lane_keys
                    )
                rows_ctr.inc(
                    self.rows(diff.new.primary.table),
                    table=diff.new.primary.table,
                )

            report.backfilled.extend(d.describe() for d in report.deficits)
            report.deficits.clear()
            report.exact = not report.hard_inexact
            report.notes.append(
                f"offline backfill spliced {bplan.total_rows} history "
                f"row(s) across {len(bplan.actions)} deficit(s)"
            )
        return OnlineState(ring=ring, bagg=bagg, sec=tuple(sec))
