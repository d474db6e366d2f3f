"""Sharded online serving plane — key-partitioned feature state on a mesh.

FeatInsight's production numbers (100+ scenarios, trillion-dimensional
feature spaces, millisecond updates) rest on OpenMLDB partitioning online
table state across nodes; managed feature stores make the same
partitioned-online-store split their core architecture.  This module is
that layer for the JAX reproduction: a :class:`ShardedOnlineStore` holds
one :class:`~repro.core.online.OnlineState` *per shard* — ring + bucket
pre-aggregates + secondary rings, stacked on a leading ``shard`` axis and
laid out over a 1-D device mesh with ``NamedSharding`` — and answers
batched requests with one compiled program vmapped over shards (GSPMD
partitions it; per-shard compute never crosses devices).

Partitioning scheme — now read from the declarative
:class:`~repro.core.layout.StoreLayout` plan (one planner decides, every
layer consumes):

* **Primary state** is partitioned by deterministic key routing.  By
  default (``hash_routing=True``) keys pass through a
  :class:`~repro.core.hashing.KeyPermutation` — a mix32-Feistel bijection
  on the key domain — and route as ``shard = perm(key) % S``,
  ``local = perm(key) // S``.  The bijection keeps the local id space
  dense (ring tables stay ``ceil(K/S)`` keys per shard) while breaking up
  adversarial/strided key patterns (all keys ≡ 0 mod S collapse onto one
  shard under raw modulo).  ``hash_routing=False`` restores raw
  ``key % S`` / ``key // S`` routing for id spaces known to be uniform.
* **Union-stream tables** share the primary key space (see
  :class:`~repro.core.storage.Database`), so tables referenced *only* by
  WINDOW UNIONs are partitioned the same way — their rows live on the
  shard that answers their key's requests.
* **LAST JOIN targets** are *replicated* on every shard (the classic
  dimension-table strategy): join keys are arbitrary request columns, so
  a lookup must succeed locally on whichever shard owns the request row.
* **Dual-use tables** (both a union stream and a join target) are
  **split** by the planner: the union-stream rows are key-partitioned
  like the primary (stored once, not S×), and only a narrow replicated
  *join slice* (the LAST JOIN argument lanes) is copied per shard —
  recovering the S× memory the replicate-everything policy used to pay.

Request path (the router's dataflow; see :mod:`repro.serve.router`) —
two flavours, bit-identical by contract:

* **Device routing** (default, ``device_routing=True``): the whole batch
  enters ONE fused jit program that computes ``shard = feistel(key) % S``
  on device (:meth:`~repro.core.hashing.KeyPermutation.device_call`),
  ranks rows within their shard (:func:`repro.kernels.route.ops.
  route_rank` — Pallas on TPU, XLA elsewhere), scatters them into a
  capacity-bucketed (S, B) per-shard grid under the ``('shard',)``
  sharding constraint, answers with the vmapped per-shard query, and
  gathers answers back to request order device-side.  Mixed
  multi-scenario batches ride the same program
  (:meth:`ShardedOnlineStore.route_and_query` — the scenario-id column
  is threaded through for the on-device (scenario, shard) histogram).
  The optimistic per-shard capacity ``B ≈ 2·ceil(N/S)`` is checked by an
  on-device overflow flag; pathological skew re-dispatches once at the
  always-safe ``B = N``, so exactness never depends on the guess.
* **Host routing** (``device_routing=False`` — the correctness oracle):
  rows are bucketed by shard on the host, padded to a shared
  power-of-two per-shard shape bucket, executed as one fused sharded
  query, and scattered back to request order on CPU.

Equality contract: every answer is **bit-identical** to the single-device
:class:`~repro.core.online.OnlineFeatureStore` under the same ingest
stream — per-key ring and bucket state depend only on that key's rows
and their order, both of which routing preserves.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.hashing import KeyPermutation
from repro.core.layout import StoreLayout, plan_layout
from repro.core.online import OnlineFeatureStore, OnlineState, state_init
from repro.kernels import note_dispatch
from repro.kernels.route.ops import route_rank

__all__ = [
    "RoutePlan",
    "build_route",
    "make_shard_mesh",
    "ShardedOnlineStore",
]


def make_shard_mesh(num_shards: int, devices=None) -> Mesh:
    """1-D ``('shard',)`` mesh over the largest divisor of ``num_shards``
    that the platform can supply (falls back to fewer devices — a 2-device
    box still runs an 8-shard store, two shards per device; one device
    runs everything, which is also the CI path without forced devices)."""
    devices = list(devices) if devices is not None else jax.devices()
    n = 1
    for d in range(min(num_shards, len(devices)), 0, -1):
        if num_shards % d == 0:
            n = d
            break
    return Mesh(np.array(devices[:n]), ("shard",))


@dataclasses.dataclass
class RoutePlan:
    """Host-side routing of one request/ingest batch across shards.

    ``idx[s]`` holds the batch row indices owned by shard ``s`` (in batch
    order, so per-key row order is preserved); ``bucket`` is the padded
    per-shard batch size (shared power-of-two shape bucket).
    """

    idx: List[np.ndarray]
    bucket: int

    @property
    def counts(self) -> np.ndarray:
        return np.array([len(ix) for ix in self.idx], np.int64)


def build_route(
    shard: np.ndarray, num_shards: int, min_bucket: int = 16
) -> RoutePlan:
    """Bucket batch rows by shard id and pick the padded shape bucket."""
    shard = np.asarray(shard)
    idx = [np.nonzero(shard == s)[0] for s in range(num_shards)]
    longest = max((len(ix) for ix in idx), default=0)
    bucket = max(min_bucket, 1 << max(longest - 1, 0).bit_length())
    return RoutePlan(idx=idx, bucket=bucket)


class ShardedOnlineStore(OnlineFeatureStore):
    """Drop-in :class:`OnlineFeatureStore` whose state is key-partitioned
    across ``num_shards`` shards on a JAX device mesh.

    Same public API (``ingest`` / ``ingest_table`` / ``query``), same
    answers bit-for-bit; ``FeatureService`` and ``verify_view`` accept it
    unchanged.  ``num_keys`` / ``secondary_num_keys`` are *global* key
    counts; per-shard tables are sized ``ceil(K/S)``.  All placement
    decisions come from the :class:`~repro.core.layout.StoreLayout`
    (computed here from the view when not passed explicitly).
    """

    def __init__(
        self,
        view,  # repro.core.view.FeatureView
        num_keys: Optional[int] = None,
        num_shards: int = 1,
        capacity: int = 256,
        num_buckets: int = 64,
        bucket_size: int = 64,
        secondary_num_keys: Optional[Dict[str, int]] = None,
        secondary_capacity: Optional[int] = None,
        ttl: Optional[int] = None,
        table_capacity: Optional[Dict[str, int]] = None,
        table_ttl: Optional[Dict[str, int]] = None,
        mesh: Optional[Mesh] = None,
        hash_routing: bool = True,
        layout: Optional[StoreLayout] = None,
        device_routing: bool = True,
    ):
        self.device_routing = bool(device_routing)
        if layout is None:
            if num_keys is None:
                raise ValueError("ShardedOnlineStore needs num_keys or layout")
            layout = plan_layout(
                [view],
                num_keys=num_keys,
                capacity=capacity,
                num_buckets=num_buckets,
                bucket_size=bucket_size,
                num_shards=num_shards,
                hash_routing=hash_routing,
                secondary_num_keys=secondary_num_keys,
                secondary_capacity=secondary_capacity,
                ttl=ttl,
                table_capacity=table_capacity,
                table_ttl=table_ttl,
            )
        if layout.num_shards is None:
            raise ValueError(
                "ShardedOnlineStore needs a sharded layout "
                "(plan_layout(..., num_shards=S))"
            )
        self._mesh_arg = mesh
        super().__init__(view, layout=layout)

    # -- layout consumption ----------------------------------------------------

    def _apply_layout(self, view, layout: StoreLayout) -> None:
        if layout.num_shards is None or layout.num_shards < 1:
            raise ValueError(
                f"sharded store needs num_shards >= 1, got "
                f"{layout.num_shards}"
            )
        S = int(layout.num_shards)
        self.num_shards = S
        self.global_num_keys = layout.num_keys
        self.hash_routing = layout.hash_routing
        self._perm: Optional[KeyPermutation] = (
            KeyPermutation(layout.perm_domain)
            if layout.perm_domain is not None
            else None
        )
        super()._apply_layout(view, layout)
        self.global_secondary_num_keys = dict(self.secondary_num_keys)
        # the mesh survives layout adoption: same shard count, same devices
        if not hasattr(self, "mesh"):
            self.mesh = (
                self._mesh_arg
                if self._mesh_arg is not None
                else make_shard_mesh(S)
            )
            self.sharding = NamedSharding(self.mesh, P("shard"))

    def _init_state(self) -> OnlineState:
        # S identical fresh per-shard states, built in place on the mesh:
        # each device materializes only its own shards (a host-side stack
        # would first hold all S on one device)
        S = self.num_shards

        def init():
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x, (S,) + x.shape),
                state_init(self.layout),
            )

        return jax.jit(init, out_shardings=self.sharding)()

    def _place_state(self, state: OnlineState) -> OnlineState:
        return jax.device_put(
            jax.tree.map(jnp.asarray, state), self.sharding
        )

    def _build_fns(self) -> None:
        # one compiled executable per path, vmapped over the shard axis;
        # GSPMD splits it across mesh devices (no cross-shard collectives
        # in the body — results gather only when fetched to host).  The
        # query fns are built through the _jit_query override below, so
        # they (and every per-scenario QueryProgram) are the vmapped
        # flavour; ingest needs its own wrapping for donation.
        super()._build_fns()
        # fused route+query executables are cached per (program, mode,
        # shape bucket) below and must re-trace after a layout adoption,
        # exactly like the base query fns
        self._fused_fns: Dict[Tuple, object] = {}
        self._ingest_fn = self._jit_ingest(self._ingest_pure)
        self._sec_ingest_fns = {
            i: self._jit_ingest(
                functools.partial(self._sec_ingest_pure, index=i)
            )
            for i in range(len(self._ring_plans))
        }

    def _jit_ingest(self, fn):
        """Per-shard ingest, vmapped over each device's local shards under
        ``shard_map``: the Pallas ingest kernel cannot be partitioned by
        GSPMD, and every argument (state and routed batch) is already
        laid out ``P('shard')``, so each device updates its own state in
        place with no collective."""
        spec = P("shard")
        return jax.jit(
            jax.shard_map(
                jax.vmap(fn), mesh=self.mesh,
                in_specs=(spec, spec, spec, spec), out_specs=spec,
                check_vma=False,  # the kernel's outputs carry no vma
            ),
            donate_argnums=(0,),
        )

    def _jit_query(self, fn):
        """Sharded query programs run vmapped over the leading shard axis
        (per-scenario programs compiled later pick this up too)."""
        return jax.jit(jax.vmap(fn))

    # -- routing ---------------------------------------------------------------

    def _check_range(self, key: np.ndarray, upper: Optional[int]) -> np.ndarray:
        """Out-of-range keys are rejected: the single-device store clamps
        them (gather semantics), the sharded store would land on a
        *different* key's state after routing — silently breaking the
        bit-identical contract — so fail loudly instead."""
        key = np.asarray(key)
        upper = self.global_num_keys if upper is None else upper
        if key.size and (key.min() < 0 or key.max() >= upper):
            raise ValueError(
                f"key out of range [0, {upper}): "
                f"[{key.min()}, {key.max()}] (sharded stores cannot clamp "
                "without routing to another key's shard)"
            )
        return key

    def _route_ids(
        self, key: np.ndarray, upper: Optional[int] = None
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Deterministic key -> (shard id, shard-local id), host-side.

        With hash routing the key first passes through the shared Feistel
        permutation; bijectivity keeps local ids collision-free per shard.
        """
        key = self._check_range(key, upper)
        routed = self._perm(key) if self._perm is not None else key
        return routed % self.num_shards, routed // self.num_shards

    def shard_of(
        self, key: np.ndarray, upper: Optional[int] = None
    ) -> np.ndarray:
        """Deterministic key -> shard id (host-side; range-checked)."""
        return self._route_ids(key, upper)[0]

    def _put(self, x: np.ndarray) -> jnp.ndarray:
        return jax.device_put(jnp.asarray(x), self.sharding)

    def _route_rows(
        self,
        plan: RoutePlan,
        arr: np.ndarray,
        pad: str = "repeat",
        sentinel: int = 0,
    ) -> np.ndarray:
        """Scatter (N, ...) batch rows into a padded (S, bucket, ...) grid.

        ``pad='repeat'`` repeats the shard's last real row (query padding:
        harmless read-only recompute, sliced off on scatter-back);
        ``pad='sentinel'`` fills the key column with an out-of-range id so
        every state scatter drops the padding (ingest padding).
        """
        arr = np.asarray(arr)
        S, B = self.num_shards, plan.bucket
        out = np.zeros((S, B) + arr.shape[1:], arr.dtype)
        if pad == "sentinel":
            out[...] = sentinel
        for s, ix in enumerate(plan.idx):
            n = len(ix)
            if not n:
                continue
            out[s, :n] = arr[ix]
            if n < B and pad == "repeat":
                out[s, n:] = arr[ix[-1]]
        return out

    def _scatter_back(
        self, plan: RoutePlan, vals: Tuple[jnp.ndarray, ...], q: int
    ) -> Tuple[np.ndarray, ...]:
        """(S, bucket) per-shard answers -> (Q,) in request order."""
        outs = []
        for v in vals:
            vh = np.asarray(v)
            o = np.zeros((q,), vh.dtype)
            for s, ix in enumerate(plan.idx):
                o[ix] = vh[s, : len(ix)]
            outs.append(o)
        return tuple(outs)

    # -- ingest ----------------------------------------------------------------

    def _sorted_route(
        self, key_h: np.ndarray, ts_h: np.ndarray, upper: Optional[int]
    ) -> Tuple[RoutePlan, np.ndarray]:
        """Routing plan + local ids for one fused ingest chunk, with every
        shard's rows in (local key, ts) order as ring/bucket ingest requires.

        Modulo routing preserves the incoming (key, ts) sort per shard
        (k1 < k2 with k1 ≡ k2 (mod S) implies k1//S < k2//S); the Feistel
        permutation scrambles key order, so hash routing stably re-sorts
        each shard's rows — same-key rows keep their arrival order, so
        per-key state (the bit-identical contract) is unaffected.  A chunk
        satisfying the bucket-span constraint still satisfies it
        shard-locally either way.
        """
        shard, local = self._route_ids(key_h, upper)
        plan = build_route(shard, self.num_shards, min_bucket=64)
        if self.hash_routing:
            plan = RoutePlan(
                idx=[
                    ix[np.lexsort((ts_h[ix], local[ix]))] for ix in plan.idx
                ],
                bucket=plan.bucket,
            )
        return plan, local

    def _ingest_padded(self, key, ts, lanes) -> None:
        """Route one fused (key, ts)-sorted chunk across shards."""
        key_h, ts_h = np.asarray(key), np.asarray(ts)
        plan, local = self._sorted_route(key_h, ts_h, None)
        k = self._route_rows(
            plan, local, pad="sentinel", sentinel=self.num_keys
        )
        t = self._route_rows(plan, ts_h, pad="repeat")
        l = self._route_rows(plan, np.asarray(lanes), pad="sentinel")
        note_dispatch("fused_ingest", self._ingest_resolved_impl())
        self.state = self._ingest_fn(
            self.state, self._put(k), self._put(t), self._put(l)
        )

    def _sec_ring_ingest_padded(self, index: int, key, ts, lanes) -> None:
        S = self.num_shards
        plan_i = self._ring_plans[index]
        if plan_i.partitioned:
            key_h, ts_h = np.asarray(key), np.asarray(ts)
            plan, local = self._sorted_route(key_h, ts_h, plan_i.num_keys)
            k = self._route_rows(
                plan, local, pad="sentinel", sentinel=plan_i.ring_keys
            )
            t = self._route_rows(plan, ts_h, pad="repeat")
            l = self._route_rows(plan, np.asarray(lanes), pad="sentinel")
        else:
            # replicated dimension table / join slice: identical fused
            # scatter on every shard keeps each replica bit-identical to
            # the single store
            key, ts, lanes = self._pad_batch(key, ts, lanes, plan_i.ring_keys)
            k, t, l = (
                np.broadcast_to(np.asarray(x), (S,) + x.shape)
                for x in (key, ts, lanes)
            )
        self.state = self._sec_ingest_fns[index](
            self.state, self._put(k), self._put(t), self._put(l)
        )

    # -- query -----------------------------------------------------------------

    def query(
        self,
        columns: Dict[str, jnp.ndarray],
        mode: str = "preagg",
        program=None,
        valid: Optional[np.ndarray] = None,
        route_info: Optional[Dict] = None,
    ) -> Dict[str, jnp.ndarray]:
        """Answer a request batch in input row order (same contract as the
        base store: {feature_name: (Q,) f32}).

        ``device_routing=True`` (default) serves the batch through the
        fused on-mesh path — routing, per-shard padding, the vmapped
        query and the gather back to request order are all one jit
        program (:meth:`_query_device_routed`).  ``device_routing=False``
        keeps the host-routed path (:meth:`_query_host_routed`) — the
        correctness oracle the parity tests compare against.

        ``valid`` optionally marks scheduler padding rows so occupancy
        accounting excludes them; ``route_info`` (a dict, filled in
        place) returns the batch's valid-masked per-shard request counts
        (``"shard_counts"``) so the router's skew histograms never
        re-hash keys.
        """
        if self.device_routing:
            return self._query_device_routed(
                columns, mode, program, valid, route_info
            )
        return self._query_host_routed(
            columns, mode, program, valid, route_info
        )

    def _query_host_routed(
        self,
        columns: Dict[str, jnp.ndarray],
        mode: str,
        program=None,
        valid: Optional[np.ndarray] = None,
        route_info: Optional[Dict] = None,
    ) -> Dict[str, jnp.ndarray]:
        """Host-routed request path (the ``device_routing=False`` oracle).

        Routing happens on the host straight from the request columns
        (normally numpy already); only the routed (S, bucket) grids are
        uploaded.  ``program`` serves one scenario's compiled sub-view
        against the shared sharded state (see
        :meth:`OnlineFeatureStore.compile_program`).

        The three stages are traced separately — ``query.route`` (host:
        shard bucketing, padding, upload), ``query.compute`` (device,
        fenced), ``query.scatter`` (host: answers back to request order) —
        so the wire-to-wire breakdown attributes host vs device time per
        stage instead of one opaque wall number.
        """
        from repro.obs import get_telemetry

        tel = get_telemetry()
        self._validate_join_cols(columns, program)
        key_h = np.asarray(columns[self.schema.key]).astype(
            np.int32, copy=False
        )
        q = int(key_h.shape[0])
        pname = program.view.name if program is not None else ""
        with tel.tracer.span(
            "query.route", mode=mode, program=pname, rows=q
        ):
            ts_h = np.asarray(columns[self.schema.ts]).astype(
                np.int32, copy=False
            )
            lane_exprs = None if program is None else program.lane_exprs
            join_cols = (
                self._join_cols if program is None else program.join_cols
            )
            lanes_h = np.asarray(self._lanes(columns, lane_exprs))
            shard, local = self._route_ids(key_h)
            plan = build_route(shard, self.num_shards, min_bucket=16)
            gkey_r = self._route_rows(plan, key_h, pad="repeat")
            args = (
                self._put(self._route_rows(plan, local, pad="repeat")),
                self._put(self._route_rows(plan, ts_h, pad="repeat")),
                self._put(self._route_rows(plan, lanes_h, pad="repeat")),
                tuple(
                    self._put(
                        self._route_rows(
                            plan,
                            np.asarray(columns[c]).astype(
                                np.int32, copy=False
                            ),
                            pad="repeat",
                        )
                    )
                    for c in join_cols
                ),
                self._put(gkey_r),                          # global key
            )
        vmask = (
            np.ones(q, bool) if valid is None else np.asarray(valid, bool)[:q]
        )
        self._note_route(tel, "host", int(vmask.sum()), q, plan.bucket)
        if route_info is not None:
            route_info["shard_counts"] = np.bincount(
                shard[vmask], minlength=self.num_shards
            ).astype(np.int64)
        fn = self._query_fn(mode, program)
        t_call = tel.clock.now()
        with tel.tracer.span(
            "query.compute", kind="device", mode=mode, program=pname,
            rows=q, padded=self.num_shards * plan.bucket,
        ) as sp:
            vals = fn(self.state, *args)
            vals = sp.fence(vals)
        self._note_query(tel, mode, program, plan.bucket, t_call)
        with tel.tracer.span("query.scatter", rows=q):
            out = self._finish_query(
                columns, self._scatter_back(plan, vals, q), program
            )
        return out

    # -- fused device-resident request path ------------------------------------

    def _route_bucket(self, m: int) -> int:
        """Optimistic per-shard grid capacity for an m-row batch: twice
        the even-split share, power-of-two (compilation caching), floored
        at 16 and capped at m (the always-safe bound — no shard can own
        more rows than the batch has).  The fused program's on-device
        overflow flag catches the rare skew beyond 2x and re-dispatches
        at the cap, so this is a latency guess, never a correctness one."""
        per = -(-m // self.num_shards)
        b = 1 << max(2 * per - 1, 0).bit_length()
        cap = 1 << max(m - 1, 0).bit_length()
        return int(min(max(16, b), max(cap, 1)))

    def _route_query_pure(
        self,
        state: OnlineState,
        key,
        ts_q,
        req_lanes,
        join_keys,
        scen,
        valid,
        *,
        bucket: int,
        num_scen: int,
        use_preagg: bool,
        wagg_order=None,
        ljoin_order=None,
        req_lane_of=None,
        join_col_index=None,
    ):
        """The fused on-mesh request program: route, pad, answer, gather.

        (a) ``shard = feistel(key) % S`` via the device Feistel mirror;
        (b) rank-within-shard (route kernel) scatters rows into the
        (S, bucket) per-shard grid, laid over the mesh by a ``('shard',)``
        sharding constraint (GSPMD keeps per-shard compute on its
        device); (c) the unchanged vmapped per-shard query answers every
        grid row; (d) answers gather back to request order device-side.
        Returns (answers, per-(scenario, shard) valid-row counts, overflow
        flag).  Unscattered grid slots hold zeros — key 0 of each shard,
        a harmless read-only recompute discarded by the gather.
        """
        S = self.num_shards
        B = bucket
        key = jnp.asarray(key, jnp.int32)
        routed = (
            self._perm.device_call(key) if self._perm is not None else key
        )
        shard = routed % S
        local = routed // S
        # the route kernel runs replicated (every device ranks the whole
        # batch) under shard_map: GSPMD cannot partition a Pallas call
        rank, counts = jax.shard_map(
            functools.partial(route_rank, num_shards=S), mesh=self.mesh,
            in_specs=P(), out_specs=(P(), P()), check_vma=False,
        )(shard)
        overflow = jnp.any(counts > B)
        slot = jnp.minimum(rank, B - 1)

        def to_grid(arr):
            g = jnp.zeros((S, B) + arr.shape[1:], arr.dtype)
            return g.at[shard, rank].set(arr, mode="drop")

        spec = NamedSharding(self.mesh, P("shard"))
        grids = jax.tree.map(
            lambda g: jax.lax.with_sharding_constraint(g, spec),
            (
                to_grid(local),
                to_grid(jnp.asarray(ts_q, jnp.int32)),
                to_grid(jnp.asarray(req_lanes, jnp.float32)),
                tuple(
                    to_grid(jnp.asarray(j, jnp.int32)) for j in join_keys
                ),
                to_grid(key),
            ),
        )
        vals = jax.vmap(
            functools.partial(
                self._query_pure,
                use_preagg=use_preagg,
                wagg_order=wagg_order,
                ljoin_order=ljoin_order,
                req_lane_of=req_lane_of,
                join_col_index=join_col_index,
            )
        )(state, *grids)
        rep = NamedSharding(self.mesh, P())
        out = tuple(
            jax.lax.with_sharding_constraint(v[shard, slot], rep)
            for v in vals
        )
        scounts = (
            jnp.zeros((num_scen, S), jnp.int32)
            .at[jnp.asarray(scen, jnp.int32), shard]
            .add(jnp.asarray(valid, jnp.int32))
        )
        return out, scounts, overflow

    def _route_query_fn(self, mode: str, program, bucket: int, num_scen: int):
        key = (
            program.view.name if program is not None else "",
            mode,
            int(bucket),
            int(num_scen),
        )
        fn = self._fused_fns.get(key)
        if fn is None:
            subset = (
                {}
                if program is None
                else dict(
                    wagg_order=program.wagg_order,
                    ljoin_order=program.ljoin_order,
                    req_lane_of=program.req_lane_of,
                    join_col_index=program.join_col_index,
                )
            )
            fn = jax.jit(
                functools.partial(
                    self._route_query_pure,
                    bucket=int(bucket),
                    num_scen=int(num_scen),
                    use_preagg=(mode != "naive"),
                    **subset,
                )
            )
            self._fused_fns[key] = fn
        return fn

    def _note_route(
        self, tel, path: str, n_rows: int, q: int, bucket: int
    ) -> None:
        """Routing telemetry shared by both paths: rows routed per path
        plus the shard-layer padding accounting."""
        pad_rows = self.num_shards * bucket - q
        m = tel.metrics
        m.counter(
            "route_rows_total",
            "request rows routed to shards, per routing path", "1",
            labels=("path",),
        ).inc(int(n_rows), path=path)
        m.counter(
            "padding_rows_total", "filler rows added to reach shape bucket",
            "1", labels=("layer",),
        ).inc(pad_rows, layer="shard")
        m.gauge(
            "padding_waste_ratio", "filler rows / bucket rows, last batch",
            "1", labels=("layer",),
        ).set(
            pad_rows / max(self.num_shards * bucket, 1), layer="shard"
        )

    def _pad_request(self, key_h, ts_h, lanes, jks, valid_h, scen):
        """Pad flat request arrays to the power-of-two shape bucket by
        repeating the last row (read-only recompute; ``valid`` marks the
        filler so device-side histograms exclude it)."""
        q = int(key_h.shape[0])
        m = max(16, 1 << max(q - 1, 0).bit_length())
        if m != q:
            pad = m - q
            key_h = np.concatenate([key_h, np.repeat(key_h[-1:], pad)])
            ts_h = np.concatenate([ts_h, np.repeat(ts_h[-1:], pad)])
            lanes = jnp.concatenate(
                [lanes, jnp.broadcast_to(lanes[-1:], (pad, lanes.shape[1]))]
            )
            jks = tuple(
                np.concatenate([j, np.repeat(j[-1:], pad)]) for j in jks
            )
            valid_h = np.concatenate([valid_h, np.zeros(pad, bool)])
            scen = np.concatenate([scen, np.repeat(scen[-1:], pad)])
        return key_h, ts_h, lanes, jks, valid_h, scen, m

    def _route_dispatch(
        self, tel, mode, program, key_h, ts_h, lanes, jks, scen, valid_h,
        m: int, num_scen: int, q: int,
    ):
        """One fused device dispatch under the ``route.device`` span (plus
        the rare overflow re-dispatch at the safe capacity, inside the
        same span so span count == batches stays 1; the re-dispatch is
        counted in ``route_redispatch_total`` and marks the span
        ``redispatched``)."""
        B = self._route_bucket(m)
        pname = program.view.name if program is not None else ""
        t_call = tel.clock.now()
        with tel.tracer.span(
            "route.device", kind="device", mode=mode, program=pname,
            rows=q, padded=m, bucket=B, shards=self.num_shards,
            redispatched=False,
        ) as sp:
            fn = self._route_query_fn(mode, program, B, num_scen)
            vals, scounts, ovf = fn(
                self.state, key_h, ts_h, lanes, jks, scen, valid_h
            )
            vals, scounts = sp.fence((vals, scounts))
            if bool(np.asarray(ovf)):
                # optimistic capacity missed (pathological skew): rerun at
                # the always-safe bucket == batch size; bit-exactness never
                # depends on the optimistic guess
                B = 1 << max(m - 1, 0).bit_length()
                sp.set(redispatched=True)
                tel.metrics.counter(
                    "route_redispatch_total",
                    "fused route dispatches re-run at full capacity after "
                    "a shard overflowed its optimistic bucket", "1",
                    labels=("program",),
                ).inc(program=pname)
                fn = self._route_query_fn(mode, program, B, num_scen)
                vals, scounts, _ = fn(
                    self.state, key_h, ts_h, lanes, jks, scen, valid_h
                )
                vals, scounts = sp.fence((vals, scounts))
        scounts_h = np.asarray(scounts, np.int64)
        self._note_route(tel, "device", scounts_h.sum(), q, B)
        self._note_query(tel, mode, program, (m, B), t_call)
        return vals, scounts_h

    def _query_device_routed(
        self,
        columns: Dict[str, jnp.ndarray],
        mode: str,
        program=None,
        valid: Optional[np.ndarray] = None,
        route_info: Optional[Dict] = None,
    ) -> Dict[str, jnp.ndarray]:
        """Single-program request path: one fused dispatch per batch.

        Host work shrinks to array conversion (``query.route`` span) and
        the post-expression finish (``query.scatter`` span); everything
        between — routing, padding, per-shard compute, gather-back — is
        the fenced ``route.device`` device span.
        """
        from repro.obs import get_telemetry

        tel = get_telemetry()
        self._validate_join_cols(columns, program)
        key_h = self._check_range(
            np.asarray(columns[self.schema.key]).astype(np.int32, copy=False),
            None,
        )
        q = int(key_h.shape[0])
        pname = program.view.name if program is not None else ""
        with tel.tracer.span(
            "query.route", mode=mode, program=pname, rows=q
        ):
            ts_h = np.asarray(columns[self.schema.ts]).astype(
                np.int32, copy=False
            )
            lane_exprs = None if program is None else program.lane_exprs
            join_cols = (
                self._join_cols if program is None else program.join_cols
            )
            lanes = jnp.asarray(self._lanes(columns, lane_exprs))
            jks = tuple(
                np.asarray(columns[c]).astype(np.int32, copy=False)
                for c in join_cols
            )
            vmask = (
                np.ones(q, bool)
                if valid is None
                else np.asarray(valid, bool)[:q]
            )
            key_p, ts_p, lanes_p, jks_p, valid_p, scen_p, m = (
                self._pad_request(
                    key_h, ts_h, lanes, jks, vmask, np.zeros(q, np.int32)
                )
            )
        vals, scounts = self._route_dispatch(
            tel, mode, program, key_p, ts_p, lanes_p, jks_p, scen_p,
            valid_p, m, 1, q,
        )
        if route_info is not None:
            route_info["shard_counts"] = scounts.sum(axis=0)
        with tel.tracer.span("query.scatter", rows=q):
            out = self._finish_query(
                columns, tuple(np.asarray(v)[:q] for v in vals), program
            )
        return out

    def route_and_query(
        self,
        columns: Dict[str, jnp.ndarray],
        scen: np.ndarray,
        num_scen: int,
        mode: str = "preagg",
        valid: Optional[np.ndarray] = None,
        route_info: Optional[Dict] = None,
    ):
        """Fused route+query for a MIXED multi-scenario batch — one device
        dispatch for rows tagged with ``scen`` (scenario ids in
        [0, num_scen)), against the merged store's FULL aggregation set.

        Every scenario of a plane shares the primary schema, so a mixed
        batch carries every column the merged program needs; computing
        the full (wagg + ljoin) set per row is bit-identical to each
        scenario's own program (per-answer compute depends only on that
        row's values).  Returns ``(vals, q)`` — the merged-order answer
        tuple still on device, (m,) arrays to slice to ``[:q]`` — and the
        caller (:meth:`repro.core.scenario.ScenarioPlane.query_mixed`)
        selects each scenario's features from the superset.  ``route_info``
        gains the on-device valid-masked ``"scenario_shard_counts"``
        (num_scen, S) histogram.
        """
        from repro.obs import get_telemetry

        if not self.device_routing:
            raise RuntimeError(
                "route_and_query is the fused device path; this store was "
                "built with device_routing=False (host-routed oracle)"
            )
        tel = get_telemetry()
        self._validate_join_cols(columns, None)
        key_h = self._check_range(
            np.asarray(columns[self.schema.key]).astype(np.int32, copy=False),
            None,
        )
        q = int(key_h.shape[0])
        scen_h = np.asarray(scen, np.int32)
        if scen_h.size and (
            scen_h.min() < 0 or scen_h.max() >= num_scen
        ):
            raise ValueError(
                f"scenario ids out of range [0, {num_scen}): "
                f"[{scen_h.min()}, {scen_h.max()}]"
            )
        with tel.tracer.span(
            "query.route", mode=mode, program="", rows=q
        ):
            ts_h = np.asarray(columns[self.schema.ts]).astype(
                np.int32, copy=False
            )
            lanes = jnp.asarray(self._lanes(columns, None))
            jks = tuple(
                np.asarray(columns[c]).astype(np.int32, copy=False)
                for c in self._join_cols
            )
            vmask = (
                np.ones(q, bool)
                if valid is None
                else np.asarray(valid, bool)[:q]
            )
            key_p, ts_p, lanes_p, jks_p, valid_p, scen_p, m = (
                self._pad_request(key_h, ts_h, lanes, jks, vmask, scen_h)
            )
        # padding repeats the last row's scenario tag but valid=False, so
        # the device histogram never counts it
        vals, scounts = self._route_dispatch(
            tel, mode, None, key_p, ts_p, lanes_p, jks_p, scen_p, valid_p,
            m, int(num_scen), q,
        )
        if route_info is not None:
            route_info["scenario_shard_counts"] = scounts
            route_info["shard_counts"] = scounts.sum(axis=0)
        return vals, q

    # -- observability ---------------------------------------------------------

    def shard_row_counts(self) -> np.ndarray:
        """Total primary rows ever ingested per shard (from ring cursors)."""
        return np.asarray(self.state.ring.cursor).sum(axis=1)
