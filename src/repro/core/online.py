"""Online feature store — FeatInsight's request-mode serving path.

OpenMLDB request mode: a request row (key, ts, values) arrives; the service
computes every feature of the view *as if that row were appended* to its
key's history, and returns the feature vector in milliseconds.  The row may
then be ingested (deployment-configurable).  Offline↔online consistency
means: the online answer for row i after ingesting rows 0..i-1 equals the
offline batch answer at row i.

Every aggregate's semantics come from the one registry in
:mod:`repro.core.aggregates`; a query is a single generic dataflow:

    lift(request row)
      ⊕ fold(primary window rows)            [raw ring, or raw boundary
                                              rows ⊕ bucket states on the
                                              pre-agg path]
      ⊕ fold(each union table's window rows) [raw secondary rings]
    → finalize

where ⊕ is the spec's associative ``combine``.  Because FIRST carries an
argmin-by-merge-order state and TOPN_FREQ a mergeable tail sketch, *every*
aggregate composes across WINDOW UNION streams — there are no per-agg
branches left in this module.

Two query paths (both pure functions, jit-compiled once per view version —
the paper's "compilation caching"):

* ``naive``  — masked fold over the raw ring (O(C) per query); the
  reproduction of the paper's un-preaggregated baseline.
* ``preagg`` — two-level composition: raw boundary rows + per-bucket
  partial states (O(C_boundary + NB)); the paper's long-window
  optimization.  Applies to every spec the bucket store persists
  (``bucket_composable``).  The Pallas kernel in
  ``repro.kernels.window_agg`` implements this same path with explicit
  VMEM tiling.

Physical layout comes from one place: the declarative
:class:`~repro.core.layout.StoreLayout` plan.  The store no longer derives
ring sizes, lane slots, or secondary-table placement itself — it *consumes*
the plan :func:`~repro.core.layout.plan_layout` computed (constructing a
store without an explicit ``layout`` plans one from its own view, which is
the legacy single-view path).  Because the plan is explicit and diffable,
a live store can :meth:`~OnlineFeatureStore.adopt_layout` an evolved plan —
carrying state buffers over by ring identity instead of rebuilding — which
is how ``ScenarioPlane.evolve`` hot-deploys new scenarios.

Window-aggregation *arguments* may be derived expressions; the store
materializes one lane per distinct argument at ingest (computed columns),
so pre-aggregation composes for derived args too — mirroring OpenMLDB
defining pre-aggregates per aggregation spec.  Evolvable layouts
(``raw_lanes=True``) additionally materialize every raw column as a lane,
so a hot-deployed view's new arguments can be synthesized from history.

Multi-table views add ring stores per referenced secondary table:
point-in-time LAST JOIN lookups (newest matching row with ``ts <= request
ts``) and WINDOW UNION aggregations (primary window combined with the
union tables' masked rings) are answered from this device state inside the
same compiled query.  Secondary rows arrive via :meth:`ingest_table`; a
table may back *several* rings (the sharded dual-use split: a partitioned
union ring plus a replicated LAST JOIN slice).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import preagg as pg
from repro.core import storage as st
from repro.core.aggregates import LANES, agg_spec
from repro.core.expr import (
    Expr,
    WindowAgg,
    collect_last_joins,
    collect_window_aggs,
    eval_rowlevel,
)
from repro.core.layout import StoreLayout, plan_layout
from repro.kernels import note_dispatch
from repro.kernels.ingest.ops import fused_ingest_apply, resolve_ingest_impl
from repro.obs import get_telemetry

__all__ = ["OnlineState", "OnlineFeatureStore", "QueryProgram", "state_init"]

_TS_MIN = np.int32(-2147483648)
_POS_MAX = np.int32(2147483647)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class OnlineState:
    """All device state of one view's online store (a pytree).

    ``sec`` holds one RingStore per secondary *ring plan*, in the store's
    ``layout.tables`` order (a dual-use table contributes two rings on a
    sharded plane).
    """

    ring: st.RingStore
    bagg: pg.BucketAgg
    sec: Tuple[st.RingStore, ...] = ()

    def tree_flatten(self):
        return (self.ring, self.bagg, self.sec), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def state_init(layout: StoreLayout) -> OnlineState:
    """Fresh (single-shard) device state for a layout plan.  A pure
    function of the plan, so ``jax.eval_shape(lambda: state_init(layout))`` sizes
    a deployment's state without allocating it."""
    lanes = max(len(layout.primary.lanes), 1)
    return OnlineState(
        ring=st.ring_init(
            layout.primary.ring_keys, layout.primary.capacity, lanes
        ),
        bagg=pg.bucket_init_plan(
            layout.bucket, layout.primary.ring_keys, lanes
        ),
        sec=tuple(
            st.ring_init(p.ring_keys, p.capacity, max(len(p.lanes), 1))
            for p in layout.tables
        ),
    )


class OnlineFeatureStore:
    """Stateful wrapper: owns an OnlineState + jit-compiled pure kernels.

    One instance per deployed feature-view version (the registry caches
    instances across versions — the paper's service-version cache).
    """

    def __init__(
        self,
        view,  # repro.core.view.FeatureView
        num_keys: Optional[int] = None,
        capacity: int = 256,
        num_buckets: int = 64,
        bucket_size: int = 64,
        secondary_num_keys: Optional[Dict[str, int]] = None,
        secondary_capacity: Optional[int] = None,
        ttl: Optional[int] = None,
        table_capacity: Optional[Dict[str, int]] = None,
        table_ttl: Optional[Dict[str, int]] = None,
        layout: Optional[StoreLayout] = None,
    ):
        if layout is None:
            if num_keys is None:
                raise ValueError("OnlineFeatureStore needs num_keys or layout")
            layout = plan_layout(
                [view],
                num_keys=num_keys,
                capacity=capacity,
                num_buckets=num_buckets,
                bucket_size=bucket_size,
                secondary_num_keys=secondary_num_keys,
                secondary_capacity=secondary_capacity,
                ttl=ttl,
                table_capacity=table_capacity,
                table_ttl=table_ttl,
            )
        self._apply_layout(view, layout)
        self.state = self._init_state()
        self._build_fns()

    # -- layout consumption ---------------------------------------------------

    def _apply_layout(self, view, layout: StoreLayout) -> None:
        """Derive every layout-dependent attribute from the plan.

        Called at construction and again by :meth:`adopt_layout` — all
        lane ids, ring indices, and placement flags live here, nowhere
        else."""
        self.view = view
        self.schema = view.schema
        self.layout = layout
        self.num_keys = layout.primary.ring_keys
        self.capacity = layout.primary.capacity
        self.num_buckets = layout.bucket.num_buckets
        self.bucket_size = layout.bucket.bucket_size
        self._ttl = layout.primary.ttl

        exprs = list(view.features.values())
        self.waggs: Dict[Tuple, WindowAgg] = collect_window_aggs(exprs)
        self._wagg_order: List[Tuple] = list(self.waggs.keys())
        self.ljoins = collect_last_joins(exprs)
        self._ljoin_order: List[Tuple] = list(self.ljoins.keys())

        # lane plan straight from the layout (wagg args, plus raw columns
        # on evolvable layouts)
        self._lane_exprs: List[Expr] = [s.expr for s in layout.primary.lanes]
        self._lane_of: Dict[Tuple, int] = {
            s.key: i for i, s in enumerate(layout.primary.lanes)
        }
        for wk, wa in self.waggs.items():
            if wa.arg.key not in self._lane_of:
                raise ValueError(
                    f"layout has no lane for window argument of "
                    f"{wa.agg.value}() in view {view.name!r}; the layout "
                    "must be planned from (a superset of) this view"
                )
        self.num_lanes = max(len(self._lane_exprs), 1)

        # union waggs whose *primary-stream* part can compose from bucket
        # pre-aggregates (secondary parts always answer from raw rings)
        self._union_preagg: Dict[Tuple, bool] = {}
        for wk, wa in self.waggs.items():
            if wa.window.mode == "range":
                need = self._window_span(wa) // self.bucket_size + 2
                if not wa.union and need > self.num_buckets:
                    feats = [
                        f for f, e in view.features.items()
                        if wk in collect_window_aggs([e])
                    ]
                    raise ValueError(
                        f"window {wa.window.size} of {wa.agg.value}() in "
                        f"feature(s) {feats} of view {view.name!r} needs "
                        f"{need} buckets of {self.bucket_size}, store "
                        f"layout has num_buckets={self.num_buckets}"
                    )
                self._union_preagg[wk] = bool(
                    wa.union
                    and need <= self.num_buckets
                    and agg_spec(wa.agg).bucket_composable
                )

        # -- secondary-ring plane (LAST JOIN + WINDOW UNION sources) --------
        self._ring_plans = layout.tables
        self._sec_names: Tuple[str, ...] = layout.table_names
        # first ring of each table (compat index for tests/verify)
        self._sec_index = {
            t: layout.rings_of(t)[0] for t in self._sec_names
        }
        self._sec_schemas = {
            t: view.database.table(t) for t in self._sec_names
        }
        self._ring_lane_exprs: List[List[Expr]] = [
            [s.expr for s in p.lanes] for p in layout.tables
        ]
        self._ring_lane_of: List[Dict[Tuple, int]] = [
            {s.key: i for i, s in enumerate(p.lanes)} for p in layout.tables
        ]
        self._union_tables: Tuple[str, ...] = ()
        for wa in self.waggs.values():
            for t in wa.union:
                if t not in self._union_tables:
                    self._union_tables += (t,)
        self._union_ring_ix = {
            t: layout.union_ring(t) for t in self._union_tables
        }
        self._join_ring_ix = {
            lj.table: layout.join_ring(lj.table)
            for lj in self.ljoins.values()
        }
        # compat view of placement (True = gathered at the shard-local key)
        self._sec_sharded: Dict[str, bool] = {
            t: any(
                p.partitioned for p in layout.tables if p.table == t
            )
            for t in self._sec_names
        }
        self.secondary_num_keys = {
            t: layout.tables[self._sec_index[t]].num_keys
            for t in self._sec_names
        }
        # request-time join-key columns (primary columns named by LAST JOINs)
        self._join_cols: Tuple[str, ...] = ()
        for lj in self.ljoins.values():
            if lj.on not in self._join_cols:
                self._join_cols += (lj.on,)
        self._join_col_index = {c: i for i, c in enumerate(self._join_cols)}

    def _init_state(self) -> OnlineState:
        return state_init(self.layout)

    def _build_fns(self) -> None:
        """(Re)wrap the pure kernels in jit.  Fresh wrappers on every
        layout adoption so stale traces (same shapes, different lane plan)
        can never answer a query."""
        # compile-time capture restarts with the wrappers: after a layout
        # adoption every (program, mode, shape-bucket) re-traces, and that
        # recompilation cost should be visible in query_compile_seconds
        self._seen_traces: set = set()
        self._ingest_fn = jax.jit(self._ingest_pure, donate_argnums=(0,))
        self._sec_ingest_fns = {
            i: jax.jit(
                functools.partial(self._sec_ingest_pure, index=i),
                donate_argnums=(0,),
            )
            for i in range(len(self._ring_plans))
        }
        # the query fns go through the overridable _jit_query hook so the
        # sharded store gets its vmapped-over-shards flavour for free —
        # including every per-scenario QueryProgram compiled against this
        # store
        self._query_naive_fn = self._jit_query(self._query_pure_naive)
        self._query_preagg_fn = self._jit_query(self._query_pure_preagg)

    # -- live evolution -------------------------------------------------------

    def adopt_layout(self, view, layout: StoreLayout, backfill=None):
        """Evolve this live store to a new (view, layout) in place.

        Diffs the old plan against ``layout``
        (:func:`~repro.core.layout.diff_layouts`), migrates every state
        buffer (carried verbatim where ring identity is unchanged;
        re-laid / lane-synthesized otherwise — see
        :mod:`repro.core.migrate`), and re-derives all layout-dependent
        attributes.  Compiled :class:`QueryProgram` s created against this
        store stay valid: they re-trace against the evolved state on
        their next call, and their trace-time subsets are matched by
        structural key, not position.

        ``backfill`` (a :class:`repro.offline.backfill.BackfillSource`)
        closes the retention horizon: state the migration could not
        reconstruct (aged-out ring rows, bucket states of lanes that
        cannot be synthesized from stored columns) is re-derived from
        offline history and spliced in *before* the new layout goes
        live — so a deficient splice refuses atomically, exactly like a
        refused migration.

        Returns the :class:`~repro.core.migrate.MigrationReport`.
        """
        from repro.core import migrate
        from repro.core.layout import diff_layouts

        tracer = get_telemetry().tracer
        with tracer.span("migrate.diff"):
            diff = diff_layouts(self.layout, layout)
        # migrate FIRST, against the still-untouched store: a refused
        # migration (unsynthesizable lane, unsupported diff) must leave
        # the live plane exactly as it was — still serving.  The routing
        # attributes migrate_state reads (permutation, shard count) are
        # invariant across any diff diff_layouts accepts.
        state, report = migrate.migrate_state(
            diff, self.state, self, backfill=backfill
        )
        if backfill is not None and report.deficits:
            # the splice also runs against the untouched store (routing /
            # permutation attrs are diff-invariant); it raises — leaving
            # the plane serving the old layout — when history cannot
            # cover a deficit
            state = backfill.splice(diff, state, report, self, view)
        self._apply_layout(view, layout)
        with tracer.span("migrate.place", kind="device") as sp:
            self.state = self._place_state(state)
            sp.fence(self.state.ring.cursor)
        self._build_fns()
        return report

    def _place_state(self, state: OnlineState) -> OnlineState:
        """Device placement of a migrated state (sharded stores re-apply
        their NamedSharding here)."""
        return jax.tree.map(jnp.asarray, state)

    # -- lane evaluation ------------------------------------------------------

    def _lanes(
        self,
        columns: Dict[str, jnp.ndarray],
        exprs: Optional[List[Expr]] = None,
    ) -> jnp.ndarray:
        """(N, L) materialized window-arg lanes from raw columns.

        ``exprs`` overrides the lane list (a scenario program's subset, so
        a request only needs the columns *its* view references).
        """
        exprs = self._lane_exprs if exprs is None else exprs
        if not exprs:
            n = jnp.asarray(columns[self.schema.key]).shape[0]
            return jnp.zeros((n, 1), jnp.float32)
        vals = [
            eval_rowlevel(e, columns, {}).astype(jnp.float32)
            for e in exprs
        ]
        return jnp.stack(vals, axis=-1)

    # -- ingest -----------------------------------------------------------------

    # fused-ingest dispatch knobs (class defaults; override per instance
    # BEFORE the first ingest, or call _build_fns() afterwards — the
    # resolved choice is baked into the jitted ingest trace).  ``auto``
    # picks the Pallas one-pass kernel on TPU, the split XLA oracle
    # elsewhere; both are bit-identical (tier-1 asserts it).
    ingest_impl: str = "auto"
    ingest_interpret: bool = False

    def _ingest_pure(self, state: OnlineState, key, ts, lanes) -> OnlineState:
        """Apply one padded batch to the six primary-store state arrays —
        the fused ingest kernel (ring scatter + bucket pre-agg merge in
        ONE pass, :mod:`repro.kernels.ingest`) or its split XLA oracle.

        Layouts persisting merge-order state families (extreme/tail)
        always take the split path: the fused kernel covers the six core
        arrays only, and the presence of ``bagg.seq`` is a static pytree
        property, so the branch is resolved at trace time."""
        if state.bagg.seq is not None:
            ring = st.ring_ingest(state.ring, key, ts, lanes)
            bagg = pg.bucket_ingest(state.bagg, key, ts, lanes)
            return OnlineState(ring=ring, bagg=bagg, sec=state.sec)
        rts, rvals, cur, bst, bbm, bid = fused_ingest_apply(
            state.ring.ts, state.ring.vals, state.ring.cursor,
            state.bagg.stats, state.bagg.bitmap, state.bagg.bucket,
            key, ts, lanes,
            bucket_size=state.bagg.size,
            impl=resolve_ingest_impl(self.ingest_impl),
            interpret=self.ingest_interpret,
        )
        ring = st.RingStore(ts=rts, vals=rvals, cursor=cur)
        bagg = pg.BucketAgg(
            stats=bst, bitmap=bbm, bucket=bid, size=state.bagg.size
        )
        return OnlineState(ring=ring, bagg=bagg, sec=state.sec)

    def ingest(self, columns: Dict[str, jnp.ndarray]) -> None:
        """Ingest a batch of raw rows (must be (key, ts)-sorted).

        ``bucket_ingest`` requires each fused batch to span fewer than
        ``num_buckets`` pre-agg buckets (a slot must receive at most one
        new bucket id per scatter).  Historical backfills can span the
        whole table's time range, so oversized batches are split here on
        bucket boundaries — each chunk stays one fused scatter.

        The whole batch is timed entry-to-queryable: the freshness clock
        stops only after a fence on the new state's ring cursor, i.e. once
        a concurrent ``query`` would actually see the rows — the paper's
        "millisecond-level feature update" metric
        (``ingest_freshness_seconds{table=}``, weighted per row).
        """
        tel = get_telemetry()
        t0 = tel.clock.now()
        with tel.tracer.span("ingest.prepare"):
            key = jnp.asarray(columns[self.schema.key], jnp.int32)
            ts = jnp.asarray(columns[self.schema.ts], jnp.int32)
            lanes = self._lanes(columns)
            ts_h = np.asarray(ts)
        if ts_h.size == 0:
            return
        with tel.tracer.span(
            "ingest", kind="device", table=self.schema.name,
            rows=int(ts_h.size),
        ) as sp:
            b = ts_h // self.bucket_size
            span_ok = (b.max() - b.min()) < self.num_buckets - 1
            if span_ok:
                self._ingest_padded(key, ts, lanes)
            else:
                # split into chunks each spanning < num_buckets buckets;
                # rows are (key, ts)-sorted, so chunk by absolute-bucket
                # epoch and re-sort each chunk by (key, ts).
                epoch = b // (self.num_buckets - 1)
                for e in np.unique(epoch):
                    idx = np.nonzero(epoch == e)[0]
                    order = idx[
                        np.lexsort((ts_h[idx], np.asarray(key)[idx]))
                    ]
                    self._ingest_padded(key[order], ts[order], lanes[order])
            sp.fence(self.state.ring.cursor)
        self._note_freshness(tel, self.schema.name, int(ts_h.size), t0)

    def _note_freshness(self, tel, table: str, n_rows: int, t0: float) -> None:
        """Record one ingest batch's entry-to-queryable freshness, counted
        once per row (call after fencing the new state)."""
        dt = tel.clock.now() - t0
        m = tel.metrics
        m.histogram(
            "ingest_freshness_seconds",
            "ingest-call-to-queryable delay per row", "s",
            labels=("table",),
        ).observe(dt, n=n_rows, table=table)
        m.counter(
            "ingest_rows_total", "rows ingested", "1", labels=("table",),
        ).inc(n_rows, table=table)

    @staticmethod
    def _pad_batch(key, ts, lanes, sentinel: int):
        """Pad a fused ingest batch to a power-of-two shape bucket so one
        compiled executable serves every batch size (the paper's compilation
        caching).  Padding rows carry an out-of-range ``sentinel`` key:
        gathers clip (harmless) and every state scatter drops them."""
        n = int(key.shape[0])
        m = max(64, 1 << (n - 1).bit_length())
        if m != n:
            pad = m - n
            key = jnp.concatenate(
                [key, jnp.full((pad,), sentinel, jnp.int32)]
            )
            ts = jnp.concatenate([ts, jnp.broadcast_to(ts[-1], (pad,))])
            lanes = jnp.concatenate(
                [lanes, jnp.zeros((pad, lanes.shape[1]), lanes.dtype)]
            )
        return key, ts, lanes

    def _ingest_resolved_impl(self) -> str:
        """Host-side mirror of :meth:`_ingest_pure`'s trace-time branch."""
        if self.state.bagg.seq is not None:
            return "xla"
        return resolve_ingest_impl(self.ingest_impl)

    def _ingest_padded(self, key, ts, lanes) -> None:
        key, ts, lanes = self._pad_batch(key, ts, lanes, self.num_keys)
        # dispatch counting lives here (host side, once per batch) — the
        # impl branch itself is baked into the jitted trace
        note_dispatch("fused_ingest", self._ingest_resolved_impl())
        self.state = self._ingest_fn(self.state, key, ts, lanes)

    # -- secondary-table ingest ----------------------------------------------

    def _sec_ingest_pure(
        self, state: OnlineState, key, ts, lanes, *, index: int
    ) -> OnlineState:
        sec = list(state.sec)
        sec[index] = st.ring_ingest(sec[index], key, ts, lanes)
        return OnlineState(ring=state.ring, bagg=state.bagg, sec=tuple(sec))

    def ingest_table(self, table: str, columns: Dict[str, jnp.ndarray]) -> None:
        """Ingest a (key, ts)-sorted batch of rows into every ring of a
        secondary table (no pre-aggregates: secondary state serves LAST
        JOIN lookups and union windows, both answered from raw rings).  A
        dual-use table on a sharded plane writes its partitioned union
        ring *and* its replicated join slice — each with that ring's own
        lane subset."""
        if table == self.schema.name:
            return self.ingest(columns)
        if table not in self._sec_index:
            raise KeyError(
                f"view {self.view.name!r} does not reference table {table!r}"
            )
        tel = get_telemetry()
        t0 = tel.clock.now()
        sch = self._sec_schemas[table]
        key = jnp.asarray(columns[sch.key], jnp.int32)
        n = int(key.shape[0])
        if n == 0:
            return
        ts = jnp.asarray(columns[sch.ts], jnp.int32)
        with tel.tracer.span(
            "ingest", kind="device", table=table, rows=n
        ) as sp:
            for i in self.layout.rings_of(table):
                exprs = self._ring_lane_exprs[i]
                if exprs:
                    lanes = jnp.stack(
                        [
                            eval_rowlevel(e, columns, {}).astype(jnp.float32)
                            for e in exprs
                        ],
                        axis=-1,
                    )
                else:
                    lanes = jnp.zeros((n, 1), jnp.float32)
                self._sec_ring_ingest_padded(i, key, ts, lanes)
            sp.fence(
                tuple(
                    self.state.sec[i].cursor
                    for i in self.layout.rings_of(table)
                )
            )
        self._note_freshness(tel, table, n, t0)

    def _sec_ring_ingest_padded(self, index: int, key, ts, lanes) -> None:
        key, ts, lanes = self._pad_batch(
            key, ts, lanes, self._ring_plans[index].ring_keys
        )
        self.state = self._sec_ingest_fns[index](self.state, key, ts, lanes)

    # -- window masks -------------------------------------------------------------

    def _window_span(self, wa: WindowAgg, ttl: Optional[int] = None) -> int:
        """Effective RANGE lookback: the window size, clamped by the
        TTL retention policy when one is set (rows older than the TTL
        are expired, so no window — RANGE or ROWS — may see them; ROWS
        windows apply the same cutoff as an eligibility mask in
        :meth:`_window_mask`).  ``ttl`` is the governing ring's policy;
        ``None`` falls back to the primary's."""
        ttl = self._ttl if ttl is None else ttl
        if ttl is not None:
            return min(wa.window.size, ttl)
        return wa.window.size

    def _window_mask(
        self, wa: WindowAgg, ts_buf, valid, ts_q,
        ttl: Optional[int] = None,
    ) -> jnp.ndarray:
        ttl = self._ttl if ttl is None else ttl
        not_future = ts_buf <= ts_q[:, None]
        if wa.window.mode == "range":
            lo = ts_q - jnp.int32(self._window_span(wa, ttl)) + 1
            return valid & not_future & (ts_buf >= lo[:, None])
        # rows mode: last (size-1) eligible rows; the request row is the
        # size-th.  Rank from the newest backwards.  TTL-expired rows are
        # not eligible (the retention policy is window-mode-independent).
        eligible = valid & not_future
        if ttl is not None:
            eligible &= ts_buf > (ts_q - jnp.int32(ttl))[:, None]
        newer = jnp.cumsum(eligible[:, ::-1].astype(jnp.int32), axis=1)[:, ::-1]
        rank_from_new = newer - eligible.astype(jnp.int32)  # 0 == newest
        return eligible & (rank_from_new < wa.window.size - 1)

    # -- secondary-state lookups ---------------------------------------------

    def _union_gathers(self, state, key, gkey, tables=None):
        """Gather each union table's ring at the request key (shared across
        every union wagg touching that table).

        ``key`` is the primary-store key (shard-local in a
        :class:`~repro.core.shard.ShardedOnlineStore`), ``gkey`` the global
        key: key-partitioned union rings hold local ids, replicated ones
        global ids.  For the single-device store both are the same array.
        ``tables`` restricts the gathers to the union tables a scenario
        program actually folds.
        """
        out = {}
        for t in (self._union_tables if tables is None else tables):
            i = self._union_ring_ix[t]
            out[t] = st.ring_gather(
                state.sec[i],
                key if self._ring_plans[i].partitioned else gkey,
            )
        return out

    def _last_join_vals(
        self, state, ts_q, join_keys, ljoin_order=None, join_col_index=None
    ) -> List[jnp.ndarray]:
        """Point-in-time LAST JOIN answers, one (Q,) vector per join.

        Newest secondary row with key == request's join key and
        ``ts <= request ts``; ties on ts resolve to the latest-ingested row
        (matching the offline stable (key, ts) sort).  ``ljoin_order``
        restricts the joins computed and ``join_col_index`` maps join
        columns into the (possibly program-scoped) ``join_keys`` tuple.
        Joins always read the table's replicated join ring (the join
        slice, on a split dual-use table).
        """
        out = []
        gathers = {}
        order = self._ljoin_order if ljoin_order is None else ljoin_order
        col_ix = (
            self._join_col_index if join_col_index is None else join_col_index
        )
        for lk in order:
            lj = self.ljoins[lk]
            jk = join_keys[col_ix[lj.on]]
            ring_ix = self._join_ring_ix[lj.table]
            gk = (ring_ix, lj.on)
            if gk not in gathers:
                gathers[gk] = st.ring_gather(state.sec[ring_ix], jk)
            ts_t, lanes_t, valid_t = gathers[gk]
            g = lanes_t[self._ring_lane_of[ring_ix][lj.arg.key]]
            m = valid_t & (ts_t <= ts_q[:, None])
            ts_m = jnp.where(m, ts_t, _TS_MIN)
            mx = jnp.max(ts_m, axis=1)
            cand = m & (ts_t == mx[:, None])
            C = ts_t.shape[1]
            pos = C - 1 - jnp.argmax(cand[:, ::-1], axis=1)
            val = jnp.take_along_axis(g, pos[:, None], axis=1)[:, 0]
            found = m.any(axis=1)
            out.append(jnp.where(found, val, jnp.float32(lj.default)))
        return out

    # -- the one query path ---------------------------------------------------

    def _preagg_parts(self, wa, state, key, ts_q, ts_buf, valid, lane):
        """Raw boundary-row mask + middle-bucket states for a RANGE window
        on the pre-agg path.

        The window decomposes into [raw head rows in the oldest partial
        bucket] + [full buckets strictly inside] + [raw tail rows in the
        request's bucket]; middles come back as persisted aggregate states
        ready for ``AggSpec.fold_buckets``, read as whole per-key rows of
        only the planes this wagg's spec folds.
        """
        B = jnp.int32(self.bucket_size)
        nb = self.num_buckets
        bucket_buf = ts_buf // B
        T = jnp.int32(self._window_span(wa))
        lo = ts_q - T + 1
        b_q = ts_q // B
        b_lo = (ts_q - T) // B
        not_future = ts_buf <= ts_q[:, None]
        in_lo = ts_buf >= lo[:, None]
        head_m = (
            valid & not_future & in_lo
            & (bucket_buf == b_lo[:, None]) & (b_lo != b_q)[:, None]
        )
        tail_m = valid & not_future & in_lo & (bucket_buf == b_q[:, None])
        raw = head_m | tail_m

        # middle full buckets b_lo+1 .. b_q-1: each plane's (K, NB) row at
        # the key, rotated so slot 0 holds bucket b_lo+1 (ids map to slots
        # mod NB), then the first M slots
        M = self._max_mid(wa)
        mids = b_lo[:, None] + 1 + jnp.arange(M, dtype=jnp.int32)[None, :]
        mvalid = mids < b_q[:, None]
        shift = (b_lo + 1) % nb
        bagg = state.bagg

        def mid(rows):
            # (..., Q, NB) rows -> (..., Q, M) middles, oldest first
            return st.rotate_rows(rows, shift)[..., :M]

        def mid_rows(x, *pin):
            # the middles of the (K, NB) plane at small position ``pin``
            return mid(x[(*pin, key)])

        ok = mvalid & (mid_rows(bagg.bucket) == mids)
        spec = agg_spec(wa.agg)
        ms, mb, ext = {}, None, None
        if spec.state == "lanes":
            ms = {
                l: mid_rows(bagg.stats, lane, LANES.index(l))
                for l in spec.lanes
            }
        elif spec.state == "bitmap":
            mb = mid_rows(bagg.bitmap, lane)
        # merge-order families read their persisted states (the arrays
        # exist whenever the layout planned them, asserted by the caller's
        # family gate): extreme at this spec's direction (0 = oldest,
        # 1 = newest), tail as (Q, M, T) newest-first per bucket
        elif spec.state == "extreme":
            d = 1 if spec.newest else 0
            ext = {
                "ts": mid_rows(bagg.xts, d),
                "pos": mid_rows(bagg.xpos, d),
                "val": mid_rows(bagg.xval, lane, d),
                "has": mid_rows(bagg.xhas, d),
            }
        elif spec.state == "tail":
            def tail_rows(x, *pin):
                rows = jnp.stack(
                    [x[(*pin, t, key)] for t in range(x.shape[-3])]
                )
                return jnp.moveaxis(mid(rows), 0, -1)

            ext = {
                "ts": tail_rows(bagg.tts),
                "pos": tail_rows(bagg.tpos),
                "val": tail_rows(bagg.tval, lane),
                "valid": tail_rows(bagg.tvalid),
            }
        return raw, ms, mb, ok, ext

    def _query_pure(self, state, key, ts_q, req_lanes, join_keys, gkey,
                    use_preagg: bool, wagg_order=None, ljoin_order=None,
                    req_lane_of=None, join_col_index=None):
        """Generic fold-then-finalize over every window aggregation.

        For each wagg: lift the request row, combine with the primary
        window's fold (raw ring rows, or boundary rows ⊕ bucket states on
        the pre-agg path), combine with each union table's fold, finalize.
        All semantics live in the :mod:`repro.core.aggregates` specs.

        ``wagg_order`` / ``ljoin_order`` restrict the computation to a
        subset of this store's aggregations and joins — how a
        :class:`QueryProgram` serves one scenario's view against state
        shared by many scenarios.  The subsets are trace-time constants, so
        each program compiles to an executable that gathers and folds only
        the lanes its view needs.  ``req_lane_of`` / ``join_col_index``
        remap window args and join columns into the program-scoped
        ``req_lanes`` / ``join_keys`` request tensors (requests carry only
        the columns *their* view references); stored-state lane ids stay
        global — the shared layout.
        """
        wagg_order = self._wagg_order if wagg_order is None else wagg_order
        req_lane_of = self._lane_of if req_lane_of is None else req_lane_of
        ts_buf, lanes_buf, valid = st.ring_gather(state.ring, key)
        union_tables = tuple(
            t
            for t in self._union_tables
            if any(t in self.waggs[wk].union for wk in wagg_order)
        )
        sec_gathers = self._union_gathers(
            state, key, gkey, tables=union_tables
        )
        out = []
        for wk in wagg_order:
            wa = self.waggs[wk]
            spec = agg_spec(wa.agg)
            lane = self._lane_of[wa.arg.key]
            g = lanes_buf[lane]
            r = req_lanes[:, req_lane_of[wa.arg.key]]
            # merge-order coordinate of the request row: primary stream
            # (rank = len(union), matching join.merge_streams), newer than
            # any stored row of the same (ts, stream)
            prim_rank = jnp.int32(len(wa.union))
            acc = spec.lift(r, ts_q, prim_rank, _POS_MAX)
            # family gate: extreme/tail specs can only compose from
            # buckets when the layout persisted their state arrays
            # (static pytree presence, resolved at trace time)
            family_ok = (
                spec.state in ("lanes", "bitmap")
                or (spec.state == "extreme" and state.bagg.xts is not None)
                or (spec.state == "tail" and state.bagg.tts is not None)
            )
            use_buckets = (
                use_preagg
                and spec.bucket_composable
                and family_ok
                and wa.window.mode == "range"
                and (not wa.union or self._union_preagg.get(wk, False))
            )
            if use_buckets:
                raw, ms, mb, ok, ext = self._preagg_parts(
                    wa, state, key, ts_q, ts_buf, valid, lane
                )
                acc = spec.combine(
                    acc, spec.fold_rows(g, ts_buf, raw, prim_rank)
                )
                acc = spec.combine(
                    acc, spec.fold_buckets(ms, mb, ok, ext=ext, rank=prim_rank)
                )
            else:
                m = self._window_mask(wa, ts_buf, valid, ts_q)
                acc = spec.combine(
                    acc, spec.fold_rows(g, ts_buf, m, prim_rank)
                )
            for rank, t in enumerate(wa.union):
                ts_t, lanes_t, valid_t = sec_gathers[t]
                ring_ix = self._union_ring_ix[t]
                lane_ix = self._ring_lane_of[ring_ix]
                g_t = lanes_t[lane_ix[wa.arg.key]]
                # union rows expire on their *own* ring's TTL when the
                # layout sets one (per-table knob); else the primary's
                m_t = self._window_mask(
                    wa, ts_t, valid_t, ts_q,
                    ttl=self._ring_plans[ring_ix].ttl,
                )
                acc = spec.combine(
                    acc, spec.fold_rows(g_t, ts_t, m_t, jnp.int32(rank))
                )
            out.append(spec.finalize(acc, n=wa.n))
        out.extend(
            self._last_join_vals(
                state, ts_q, join_keys, ljoin_order, join_col_index
            )
        )
        return tuple(out)

    def _query_pure_naive(self, state, key, ts_q, req_lanes, join_keys, gkey):
        return self._query_pure(
            state, key, ts_q, req_lanes, join_keys, gkey, use_preagg=False
        )

    def _query_pure_preagg(self, state, key, ts_q, req_lanes, join_keys, gkey):
        return self._query_pure(
            state, key, ts_q, req_lanes, join_keys, gkey, use_preagg=True
        )

    def _jit_query(self, fn):
        """How this store turns a pure query fn into a compiled one; the
        sharded store overrides it to vmap over the leading shard axis
        first, so per-scenario programs inherit the right flavour."""
        return jax.jit(fn)

    def compile_program(self, view) -> "QueryProgram":
        """Compile a per-scenario query program for ``view`` against this
        store's (possibly shared, multi-scenario) state."""
        return QueryProgram(self, view)

    def _max_mid(self, wa: WindowAgg) -> int:
        """Static bound on middle-bucket count for a window."""
        return max(
            1,
            min(
                self.num_buckets,
                self._window_span(wa) // self.bucket_size + 1,
            ),
        )

    # -- public query ---------------------------------------------------------------------

    @classmethod
    def create(
        cls,
        view,
        *,
        num_keys: Optional[int] = None,
        num_shards: Optional[int] = None,
        layout: Optional[StoreLayout] = None,
        **store_kwargs,
    ) -> "OnlineFeatureStore":
        """Factory shared by every deployment path (services, verify_view):
        a single-device store, or a :class:`~repro.core.shard.
        ShardedOnlineStore` when ``num_shards`` is given (or the layout
        plans shards)."""
        if layout is not None and layout.num_shards is not None:
            num_shards = layout.num_shards
        if num_shards is not None:
            from repro.core.shard import ShardedOnlineStore

            return ShardedOnlineStore(
                view,
                num_keys=num_keys,
                num_shards=num_shards,
                layout=layout,
                **store_kwargs,
            )
        # routing flavour only exists on the sharded store; a single-device
        # deployment accepts (and ignores) it so build(**kwargs) is uniform
        store_kwargs.pop("device_routing", None)
        return OnlineFeatureStore(
            view, num_keys=num_keys, layout=layout, **store_kwargs
        )

    def _validate_join_cols(
        self,
        columns: Dict[str, jnp.ndarray],
        program: Optional["QueryProgram"] = None,
    ) -> None:
        cols = self._join_cols if program is None else program.join_cols
        view = self.view if program is None else program.view
        for c in cols:
            if c not in columns:
                raise KeyError(
                    f"request rows must carry join-key column {c!r} "
                    f"(LAST JOIN on {c!r} in view {view.name!r})"
                )

    def _request_arrays(
        self,
        columns: Dict[str, jnp.ndarray],
        program: Optional["QueryProgram"] = None,
    ):
        """(key, ts, lanes, join_keys) request tensors, join cols validated.

        With a ``program``, lanes and join keys are scoped to that
        scenario's view — requests need only the columns it references,
        exactly as against a dedicated single-view store.
        """
        self._validate_join_cols(columns, program)
        key = jnp.asarray(columns[self.schema.key], jnp.int32)
        ts_q = jnp.asarray(columns[self.schema.ts], jnp.int32)
        lane_exprs = None if program is None else program.lane_exprs
        join_cols = self._join_cols if program is None else program.join_cols
        req_lanes = self._lanes(columns, lane_exprs)
        join_keys = tuple(
            jnp.asarray(columns[c], jnp.int32) for c in join_cols
        )
        return key, ts_q, req_lanes, join_keys

    def _finish_query(
        self, columns, vals, program: Optional["QueryProgram"] = None
    ) -> Dict[str, jnp.ndarray]:
        """Pre-agg answers -> named features via row-level post-expressions."""
        if program is None:
            keys = self._wagg_order + self._ljoin_order
            features = self.view.features
        else:
            keys = list(program.wagg_order) + list(program.ljoin_order)
            features = program.view.features
        pre_values = dict(zip(keys, vals))
        out: Dict[str, jnp.ndarray] = {}
        for fname, fexpr in features.items():
            out[fname] = eval_rowlevel(fexpr, columns, pre_values)
        return out

    def _query_fn(self, mode: str, program: Optional["QueryProgram"]):
        if program is not None:
            return program.fn(mode)
        return self._query_naive_fn if mode == "naive" else self._query_preagg_fn

    def ingest_row_counts(self) -> Dict[str, int]:
        """Rows stored per table, summed over all device state (from ring
        cursors, so counts are rows *ever ingested*, not current capacity).

        On a sharded store a key-partitioned table counts each row once
        (rows live on exactly one shard) while a replicated LAST JOIN
        target counts ``num_shards``× (one copy per shard).  A split
        dual-use table counts its partitioned union part once plus
        ``num_shards``× its replicated join slice — exactly the
        storage-cost accounting the dual-use partitioning claim is stated
        in.
        """
        counts = {self.schema.name: int(np.sum(self.state.ring.cursor))}
        for i, p in enumerate(self._ring_plans):
            counts[p.table] = counts.get(p.table, 0) + int(
                np.sum(self.state.sec[i].cursor)
            )
        return counts

    def ring_row_counts(self) -> Dict[Tuple[str, str], np.ndarray]:
        """Per-ring stored row totals, keyed ``(table, placement)``.

        Single-device stores report one total per ring; the sharded
        override reports a per-shard vector — the observable behind the
        dual-use assertion that union-stream rows are stored once, not
        once per shard.
        """
        out = {
            (self.schema.name, "partitioned" if self.layout.primary.partitioned
             else "replicated"): np.asarray(self.state.ring.cursor).sum(-1)
        }
        for i, p in enumerate(self._ring_plans):
            k = (p.table, "partitioned" if p.partitioned else "replicated")
            out[k] = np.asarray(self.state.sec[i].cursor).sum(-1)
        return out

    def record_gauges(self) -> None:
        """Publish pull-style state gauges into the installed telemetry:
        per-ring occupancy (stored rows / capacity), capacity-evicted row
        totals, and — where the layout sets a TTL — how many stored rows
        are already past it (logically expired, serving no window).

        Call at scrape/snapshot time; gauges reflect the store *now*.
        """
        tel = get_telemetry()
        m = tel.metrics

        def _ring(ring, plan) -> None:
            table = plan.table
            placement = "partitioned" if plan.partitioned else "replicated"
            cur = np.asarray(ring.cursor)          # (..., K)
            C = int(ring.ts.shape[-1])
            stored = np.minimum(cur, C)
            cap = cur.size * C
            m.gauge(
                "ring_occupancy_ratio", "stored rows / ring capacity", "1",
                labels=("table", "placement"),
            ).set(float(stored.sum()) / max(cap, 1),
                  table=table, placement=placement)
            m.gauge(
                "ring_evicted_rows_total",
                "rows overwritten by ring wraparound (capacity eviction)",
                "1", labels=("table", "placement"),
            ).set(float(np.maximum(cur - C, 0).sum()),
                  table=table, placement=placement)
            if plan.ttl:
                ts = np.asarray(ring.ts)           # (..., K, C)
                valid = np.arange(C) < cur[..., None]
                if valid.any():
                    now_ts = int(ts[valid].max())
                    expired = int(
                        (valid & (ts < now_ts - int(plan.ttl))).sum()
                    )
                else:
                    expired = 0
                m.gauge(
                    "ring_ttl_expired_rows",
                    "stored rows older than the layout TTL", "1",
                    labels=("table",),
                ).set(float(expired), table=table)

        _ring(self.state.ring, self.layout.primary)
        for i, p in enumerate(self._ring_plans):
            _ring(self.state.sec[i], p)

    def query(
        self,
        columns: Dict[str, jnp.ndarray],
        mode: str = "preagg",
        program: Optional["QueryProgram"] = None,
        valid: Optional[np.ndarray] = None,
        route_info: Optional[Dict] = None,
    ) -> Dict[str, jnp.ndarray]:
        """Compute all view features for a batch of request rows.

        columns: raw request columns incl. key, ts, and any LAST JOIN key
        columns; (Q,) each.  Returns {feature_name: (Q,) f32}.

        ``program`` answers with a per-scenario :class:`QueryProgram`
        compiled by :meth:`compile_program` instead of this store's full
        view — the multi-scenario serving path.

        ``valid`` optionally masks scheduler padding rows and
        ``route_info`` (dict, filled in place) reports per-shard request
        counts — one shard here; the sharded store computes the real
        histogram as a routing by-product so callers never re-hash keys.
        """
        tel = get_telemetry()
        with tel.tracer.span("query.prepare"):
            if route_info is not None:
                n_real = (
                    int(np.asarray(valid, bool).sum())
                    if valid is not None
                    else len(np.asarray(columns[self.schema.key]))
                )
                route_info["shard_counts"] = np.array([n_real], np.int64)
            key, ts_q, req_lanes, join_keys = self._request_arrays(
                columns, program
            )
            fn = self._query_fn(mode, program)
        # pad the request to a power-of-two shape bucket (compilation
        # caching: one executable per bucket, not per request size)
        q = int(key.shape[0])
        m = max(16, 1 << (q - 1).bit_length())
        t_call = tel.clock.now()
        with tel.tracer.span(
            "query.compute", kind="device", mode=mode,
            program=program.view.name if program is not None else "",
            rows=q, padded=m,
        ) as sp:
            if m != q:
                pad = m - q
                key_p = jnp.concatenate(
                    [key, jnp.broadcast_to(key[-1], (pad,))]
                )
                ts_p = jnp.concatenate(
                    [ts_q, jnp.broadcast_to(ts_q[-1], (pad,))]
                )
                lanes_p = jnp.concatenate(
                    [req_lanes,
                     jnp.broadcast_to(req_lanes[-1:],
                                      (pad, req_lanes.shape[1]))]
                )
                jk_p = tuple(
                    jnp.concatenate([j, jnp.broadcast_to(j[-1], (pad,))])
                    for j in join_keys
                )
                vals = fn(self.state, key_p, ts_p, lanes_p, jk_p, key_p)
                vals = tuple(v[:q] for v in vals)
            else:
                vals = fn(self.state, key, ts_q, req_lanes, join_keys, key)
            vals = sp.fence(vals)
        with tel.tracer.span("query.finish"):
            self._note_query(tel, mode, program, m, t_call)
            return self._finish_query(columns, vals, program)

    def _note_query(self, tel, mode, program, padded_rows, t_call) -> None:
        """Query-side metrics: first-trace compile capture per
        (program, mode, shape bucket) and preagg hit/fallback counters.
        ``padded_rows`` is any hashable shape key — an int bucket, or the
        fused device path's (batch, bucket) pair."""
        name = program.view.name if program is not None else self.view.name
        trace_key = (
            name,
            mode,
            padded_rows if isinstance(padded_rows, tuple) else int(padded_rows),
        )
        if trace_key not in self._seen_traces:
            self._seen_traces.add(trace_key)
            # first call at this shape = trace + XLA compile (+ one
            # execution, negligible next to compilation at smoke sizes)
            tel.metrics.histogram(
                "query_compile_seconds",
                "first-trace wall time per (program, mode, shape bucket)",
                "s", labels=("program", "mode"),
            ).observe(
                tel.clock.now() - t_call, program=name, mode=mode
            )
        wagg_order = (
            self._wagg_order if program is None else program.wagg_order
        )
        hits = tel.metrics.counter(
            "preagg_hits_total",
            "window aggs answered from bucket pre-aggregates", "1",
            labels=("agg",),
        )
        falls = tel.metrics.counter(
            "preagg_fallback_total",
            "window aggs falling back to the raw ring fold", "1",
            labels=("agg",),
        )
        for wk in wagg_order:
            wa = self.waggs[wk]
            spec = agg_spec(wa.agg)
            # host-side mirror of _query_pure's trace-time use_buckets
            family_ok = (
                spec.state in ("lanes", "bitmap")
                or (spec.state == "extreme"
                    and self.state.bagg.xts is not None)
                or (spec.state == "tail"
                    and self.state.bagg.tts is not None)
            )
            hit = (
                mode != "naive"
                and spec.bucket_composable
                and family_ok
                and wa.window.mode == "range"
                and (not wa.union or self._union_preagg.get(wk, False))
            )
            (hits if hit else falls).inc(agg=wa.agg.value)


class QueryProgram:
    """One scenario's compiled query against a shared store.

    The multi-scenario plane (:mod:`repro.core.scenario`) deploys N feature
    views on ONE store whose lane plan is the union of every view's window
    arguments.  A QueryProgram is the per-view slice of that store: the
    view's window aggregations and LAST JOINs as trace-time subsets, jitted
    through the store's :meth:`OnlineFeatureStore._jit_query` hook (so a
    sharded store yields a vmapped-over-shards program).  The compiled
    executable gathers and folds only the lanes its view references — the
    other scenarios' state is carried along untouched.

    Every (wagg, ljoin) key of the view must exist in the store; the
    store's answers through a program are bit-identical to a dedicated
    single-view store fed the same stream (asserted in
    ``tests/test_scenario.py``).  Programs survive
    :meth:`OnlineFeatureStore.adopt_layout`: their subsets are structural
    keys, so they re-trace correctly against the evolved layout.
    """

    def __init__(self, store: OnlineFeatureStore, view):
        exprs = list(view.features.values())
        self.view = view
        waggs = collect_window_aggs(exprs)
        ljoins = collect_last_joins(exprs)
        self.wagg_order: Tuple[Tuple, ...] = tuple(waggs.keys())
        self.ljoin_order: Tuple[Tuple, ...] = tuple(ljoins.keys())
        missing = [k for k in self.wagg_order if k not in store.waggs]
        missing += [k for k in self.ljoin_order if k not in store.ljoins]
        if missing:
            raise ValueError(
                f"view {view.name!r} is not a sub-view of store view "
                f"{store.view.name!r}: {len(missing)} aggregation(s)/join(s) "
                f"missing from the shared lane plan (first: {missing[0]!r})"
            )
        # program-scoped request tensors: requests carry only THIS view's
        # columns, so lanes and join keys get their own (smaller) layout;
        # stored-state lane ids stay global (the shared layout)
        self.lane_exprs: List[Expr] = []
        self.req_lane_of: Dict[Tuple, int] = {}
        for wa in waggs.values():
            if wa.arg.key not in self.req_lane_of:
                self.req_lane_of[wa.arg.key] = len(self.lane_exprs)
                self.lane_exprs.append(wa.arg)
        self.join_cols: Tuple[str, ...] = ()
        for lj in ljoins.values():
            if lj.on not in self.join_cols:
                self.join_cols += (lj.on,)
        self.join_col_index = {c: i for i, c in enumerate(self.join_cols)}
        subset = dict(
            wagg_order=self.wagg_order,
            ljoin_order=self.ljoin_order,
            req_lane_of=self.req_lane_of,
            join_col_index=self.join_col_index,
        )
        self._naive_fn = store._jit_query(
            functools.partial(store._query_pure, use_preagg=False, **subset)
        )
        self._preagg_fn = store._jit_query(
            functools.partial(store._query_pure, use_preagg=True, **subset)
        )

    def fn(self, mode: str):
        return self._naive_fn if mode == "naive" else self._preagg_fn
