"""Online feature service + model serving — FeatInsight §3.1 step 4.

``FeatureService`` is the paper's deployment unit: a named, versioned
view bound to an online store, answering request rows with feature
vectors under a latency budget.  ``ScoringService`` composes it with a
model (feature vector -> signature embedding -> transformer -> score),
the fraud-detection layout of §3.3.

``BatchScheduler`` is the serving loop's micro-batcher: requests are
coalesced up to ``max_batch`` or ``max_wait_us`` (whichever first) so the
jit'd query executes at a fixed batch shape (padding to the shape bucket
keeps one compiled executable per bucket — compilation caching again).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.online import OnlineFeatureStore
from repro.core.view import FeatureRegistry, FeatureView
from repro.obs import QUEUE_WAIT_BUCKETS_S, get_telemetry

__all__ = [
    "FeatureService",
    "MultiScenarioService",
    "BatchScheduler",
    "ScoringService",
    "SCENARIO_COL",
]

# meta column carrying the per-row scenario tag of a mixed batch (set by
# ShardRouter.submit, consumed by MultiScenarioService.request_mixed)
SCENARIO_COL = "__scenario__"


@dataclasses.dataclass
class ServiceStats:
    """Request counters + latency distributions.

    The paper's latency claims are *tail*-latency claims (<20 ms at
    QPS > 1000), so the stats keep rings of recent samples and report
    percentiles, not just the mean.

    Two distributions live here:

    * **per-request** (``request_p50_ms`` / ``request_p95_ms`` /
      ``request_p99_ms``): one sample per request — queue wait plus the
      wall time of the batch that served it — so a 64-request batch
      contributes 64 samples and the tail reflects what a user request
      actually experienced.  This is the authoritative latency metric.
    * **per-batch** (``p50_ms`` / ``p95_ms`` / ``p99_ms``): one sample per
      batch wall time, *unweighted* by batch size.  Deprecated — kept
      working for existing dashboards/tests, but it under-weights busy
      batches (a 1-row batch counts the same as a 256-row one) and
      excludes queue wait.  New code should read the request percentiles.
    """

    requests: int = 0
    batches: int = 0
    total_latency_s: float = 0.0
    window: int = 1024
    recent_latency_s: List[float] = dataclasses.field(
        default_factory=list, repr=False
    )
    recent_request_latency_s: List[float] = dataclasses.field(
        default_factory=list, repr=False
    )

    def observe(self, latency_s: float, n_requests: int) -> None:
        """Record one served batch (batch wall time + request count).

        Without per-request wait attribution, each of the batch's
        requests is also credited the batch wall time in the per-request
        ring; :meth:`observe_requests` overrides that with true
        wait-inclusive samples when the caller has them.
        """
        self.requests += n_requests
        self.batches += 1
        self.total_latency_s += latency_s
        self.recent_latency_s.append(latency_s)
        if len(self.recent_latency_s) > self.window:
            del self.recent_latency_s[: len(self.recent_latency_s) - self.window]

    def observe_requests(self, latencies_s: Sequence[float]) -> None:
        """Record per-request end-to-end latencies (wait + batch wall)."""
        self.recent_request_latency_s.extend(float(x) for x in latencies_s)
        if len(self.recent_request_latency_s) > self.window:
            del self.recent_request_latency_s[
                : len(self.recent_request_latency_s) - self.window
            ]

    @property
    def mean_latency_ms(self) -> float:
        return 1e3 * self.total_latency_s / max(self.batches, 1)

    def percentile_ms(self, p: float) -> float:
        """DEPRECATED batch-latency percentile (unweighted by batch size)."""
        if not self.recent_latency_s:
            return 0.0
        return 1e3 * float(np.percentile(np.asarray(self.recent_latency_s), p))

    def request_percentile_ms(self, p: float) -> float:
        """Per-request latency percentile (queue wait + batch wall time)."""
        if not self.recent_request_latency_s:
            return 0.0
        return 1e3 * float(
            np.percentile(np.asarray(self.recent_request_latency_s), p)
        )

    @property
    def p50_ms(self) -> float:
        return self.percentile_ms(50.0)

    @property
    def p95_ms(self) -> float:
        return self.percentile_ms(95.0)

    @property
    def p99_ms(self) -> float:
        return self.percentile_ms(99.0)

    @property
    def request_p50_ms(self) -> float:
        return self.request_percentile_ms(50.0)

    @property
    def request_p95_ms(self) -> float:
        return self.request_percentile_ms(95.0)

    @property
    def request_p99_ms(self) -> float:
        return self.request_percentile_ms(99.0)


class FeatureService:
    """A deployed (view, version) answering online feature requests."""

    def __init__(
        self,
        name: str,
        view: FeatureView,
        store: OnlineFeatureStore,
        registry: Optional[FeatureRegistry] = None,
        mode: str = "preagg",
    ):
        self.name = name
        self.view = view
        self.store = store
        self.mode = mode
        self.registry = registry
        self.stats = ServiceStats()
        if registry is not None:
            registry.deploy(name, view.name, view.version)

    @classmethod
    def build(
        cls,
        name: str,
        view: FeatureView,
        *,
        num_keys: int,
        registry: Optional[FeatureRegistry] = None,
        mode: str = "preagg",
        sharded: bool = False,
        num_shards: Optional[int] = None,
        **store_kwargs,
    ) -> "FeatureService":
        """Construct the service together with its online store.

        ``sharded=True`` deploys on a :class:`~repro.core.shard.
        ShardedOnlineStore` — view state key-partitioned across
        ``num_shards`` shards (default: one per local device) on a device
        mesh, answers bit-identical to the single-device store.  The
        request path is unchanged; compose with :class:`ScoringService`
        and :class:`~repro.serve.router.ShardRouter` as usual.
        """
        if not sharded and num_shards is not None:
            raise ValueError("num_shards requires sharded=True")
        if sharded and num_shards is None:
            num_shards = max(len(jax.devices()), 1)
        store = OnlineFeatureStore.create(
            view, num_keys=num_keys, num_shards=num_shards, **store_kwargs
        )
        return cls(name, view, store, registry=registry, mode=mode)

    @classmethod
    def build_multi(
        cls,
        name: str,
        views: Sequence[FeatureView],
        *,
        num_keys: int,
        registry: Optional[FeatureRegistry] = None,
        mode: str = "preagg",
        sharded: bool = False,
        num_shards: Optional[int] = None,
        **store_kwargs,
    ) -> "MultiScenarioService":
        """Deploy N scenario views as ONE service on ONE shared store.

        The views are fused into a :class:`~repro.core.scenario.
        ScenarioPlane`: shared tables are ingested and stored once (per
        shard, with ``sharded=True`` — all scenarios live on a single
        ``('shard',)`` mesh), and each view queries through its own
        compiled program, bit-identical to a dedicated single-view store.
        Requests carry a ``scenario=`` tag:
        ``svc.request(rows, scenario="fraud")``; per-scenario latency/QPS
        lands in ``svc.scenario_stats[...]`` alongside the aggregate
        ``svc.stats``.
        """
        from repro.core.scenario import ScenarioPlane

        if not sharded and num_shards is not None:
            raise ValueError("num_shards requires sharded=True")
        if sharded and num_shards is None:
            num_shards = max(len(jax.devices()), 1)
        plane = ScenarioPlane(
            views,
            num_keys=num_keys,
            num_shards=num_shards,
            name=name,
            **store_kwargs,
        )
        return MultiScenarioService(name, plane, registry=registry, mode=mode)

    # -- per-request hooks (MultiScenarioService overrides both) -------------

    def _compute(
        self,
        rows: Dict[str, np.ndarray],
        scenario: Optional[str],
        valid: Optional[np.ndarray] = None,
        route_info: Optional[Dict] = None,
    ) -> Dict[str, np.ndarray]:
        if scenario is not None:
            raise ValueError(
                f"service {self.name!r} is single-scenario; scenario= tags "
                "need a FeatureService.build_multi deployment"
            )
        return self.store.query(
            rows, mode=self.mode, valid=valid, route_info=route_info
        )

    def _observe(
        self,
        latency_s: float,
        n_requests: int,
        scenario: Optional[str],
        request_latencies_s: Optional[np.ndarray] = None,
    ) -> None:
        self.stats.observe(latency_s, n_requests)
        if request_latencies_s is not None:
            self.stats.observe_requests(request_latencies_s)

    def request(self, rows: Dict[str, np.ndarray],
                ingest: bool = True,
                scenario: Optional[str] = None,
                route_info: Optional[Dict] = None) -> Dict[str, np.ndarray]:
        """Compute features for a batch of request rows; optionally ingest
        them afterwards (the online-learning pattern of the paper).

        Batches from :class:`BatchScheduler` carry a ``__valid__`` mask over
        padding rows (the last real row repeated up to the shape bucket)
        and a ``__wait_us__`` per-row queue-wait column.  All ``__``-meta
        columns are stripped before querying; the mask is honored on ingest
        — padding rows are duplicates of a real row, so ingesting them
        would corrupt window state (double-counted sums, inflated counts).
        The wait column attributes per-request latency: each request's
        sample is its queue wait plus this batch's wall time.

        ``scenario`` selects which view answers on a multi-scenario
        deployment (see :meth:`build_multi`); ingested rows land in the
        shared store once, serving every scenario.  ``route_info`` (dict,
        filled in place) surfaces the store's per-shard routing counts to
        the caller — the router's skew histograms read them instead of
        re-hashing keys.
        """
        tel = get_telemetry()
        t0 = tel.clock.now()
        valid = rows.get("__valid__")
        wait_us = rows.get("__wait_us__")
        rows = {c: v for c, v in rows.items() if not c.startswith("__")}
        n_rows = len(next(iter(rows.values())))
        n_real = int(np.asarray(valid, bool).sum()) if valid is not None else n_rows
        with tel.tracer.span(
            "request", service=self.name,
            scenario=scenario or "", rows=n_real,
        ):
            out = self._compute(
                rows, scenario, valid=valid, route_info=route_info
            )
            with tel.tracer.span("request.fetch"):
                out = {k: np.asarray(v) for k, v in out.items()}
            if ingest:
                real = rows
                if valid is not None:
                    valid = np.asarray(valid, bool)
                    real = {c: np.asarray(v)[valid] for c, v in rows.items()}
                if len(next(iter(real.values()))):
                    key = np.asarray(real[self.view.schema.key])
                    ts = np.asarray(real[self.view.schema.ts])
                    order = np.lexsort((ts, key))
                    self.store.ingest(
                        {c: np.asarray(v)[order] for c, v in real.items()}
                    )
        dt = tel.clock.now() - t0
        with tel.tracer.span("request.record"):
            # per-request latency = that request's queue wait + batch wall
            if wait_us is not None:
                waits_s = np.asarray(wait_us, np.float64)[:n_rows] / 1e6
                if valid is not None:
                    waits_s = waits_s[np.asarray(valid, bool)]
                else:
                    waits_s = waits_s[:n_real]
            else:
                waits_s = np.zeros(n_real, np.float64)
            req_lat = waits_s + dt
            tel.metrics.counter(
                "service_requests_total", "requests served", "1",
                labels=("service", "scenario"),
            ).inc(n_real, service=self.name, scenario=scenario or "")
            self._record_batch(
                tel, req_lat, waits_s if wait_us is not None else None,
                n_real, n_rows if valid is not None else 0,
            )
            self._observe(dt, n_real, scenario, req_lat)
        return out

    def _record_batch(
        self,
        tel,
        req_lat: np.ndarray,
        waits_s: Optional[np.ndarray],
        n_real: int,
        n_padded: int,
    ) -> None:
        """The per-request latency and queue-wait histograms and the batch
        occupancy gauge of one served batch (``waits_s`` None: the batch
        carried no wait column; ``n_padded`` 0: no padding mask)."""
        m = tel.metrics
        m.histogram(
            "request_latency_seconds",
            "per-request latency (queue wait + batch wall)", "s",
            labels=("service",),
        ).observe_array(req_lat, service=self.name)
        if waits_s is not None and len(waits_s):
            m.histogram(
                "queue_wait_seconds", "scheduler queue wait per request",
                "s", labels=("service",), bounds=QUEUE_WAIT_BUCKETS_S,
            ).observe_array(waits_s, service=self.name)
        if n_padded:
            m.gauge(
                "batch_occupancy_ratio",
                "real rows / padded batch rows, last batch", "1",
                labels=("service",),
            ).set(n_real / n_padded, service=self.name)

    def feature_matrix(
        self, rows: Dict[str, np.ndarray], scenario: Optional[str] = None
    ) -> np.ndarray:
        out = self.request(rows, ingest=False, scenario=scenario)
        feats = self._scenario_features(scenario)
        return np.stack([out[f] for f in feats], axis=-1)

    def _scenario_features(self, scenario: Optional[str]) -> Sequence[str]:
        return self.view.features


class MultiScenarioService(FeatureService):
    """One deployment serving N scenarios from one shared store and mesh.

    ``view``/``store`` are the plane's merged view and shared store, so
    everything written against :class:`FeatureService` (routers, stats
    consumers, ingest paths) keeps working; queries additionally take the
    ``scenario=`` tag and answer with that view's features by their
    original (un-prefixed) names.  Deploy records land in the registry as
    ``"<service>:<scenario>"`` per scenario.
    """

    def __init__(
        self,
        name: str,
        plane,  # repro.core.scenario.ScenarioPlane
        registry: Optional[FeatureRegistry] = None,
        mode: str = "preagg",
    ):
        self.plane = plane
        super().__init__(name, plane.merged, plane.store, mode=mode)
        self.registry = registry
        self.scenario_stats: Dict[str, ServiceStats] = {
            s: ServiceStats() for s in plane.scenarios
        }
        if registry is not None:
            for s, v in plane.views.items():
                registry.deploy(f"{name}:{s}", v.name, v.version)

    @property
    def scenarios(self) -> List[str]:
        return self.plane.scenarios

    def hot_deploy(self, view: FeatureView, backfill=None, **plan_overrides):
        """Deploy one more scenario onto the LIVE plane — no rebuild, no
        re-ingest, no downtime for the scenarios already serving.

        Drives :meth:`~repro.core.scenario.ScenarioPlane.evolve`: the
        layout planner re-plans for ``views + [view]``, the running
        store's state migrates to the new plan (carried buffers verbatim,
        new lanes synthesized from history), and only the new view's
        :class:`~repro.core.online.QueryProgram` is compiled.  The
        deployment is recorded in the registry as
        ``"<service>:<scenario>"`` with a ``hot deploy`` description
        (the view is registered first if the registry does not know it),
        and a fresh per-scenario :class:`ServiceStats` starts counting.

        ``backfill`` (a :class:`repro.offline.backfill.BackfillSource`)
        lets the deployment reach beyond the rings' retention horizon:
        aged-out state the migration cannot reconstruct is re-derived
        from offline history and spliced in, keeping ``report.exact``.

        Returns the :class:`~repro.core.migrate.MigrationReport`.
        """
        if view.name in self.plane.views:
            raise ValueError(
                f"scenario {view.name!r} is already deployed on "
                f"{self.name!r}; hot_deploy adds new scenarios"
            )
        tel = get_telemetry()
        with tel.tracer.span(
            "hot_deploy", service=self.name, scenario=view.name
        ):
            report = self.plane.evolve(
                list(self.plane.views.values()) + [view],
                backfill=backfill, **plan_overrides,
            )
        tel.metrics.counter(
            "hot_deploys_total", "scenarios hot-deployed onto live planes",
            "1", labels=("service",),
        ).inc(service=self.name)
        self.view = self.plane.merged
        self.scenario_stats.setdefault(view.name, ServiceStats())
        if self.registry is not None:
            try:
                self.registry.get(view.name, view.version)
            except KeyError:
                self.registry.register(view)
            self.registry.deploy(
                f"{self.name}:{view.name}",
                view.name,
                view.version,
                description="hot deploy (live plane evolution)",
            )
        return report

    def _compute(self, rows, scenario, valid=None, route_info=None):
        if scenario is None:
            raise ValueError(
                f"multi-scenario service {self.name!r} needs scenario= "
                f"(one of {self.scenarios})"
            )
        return self.plane.query(
            scenario, rows, mode=self.mode, valid=valid, route_info=route_info
        )

    def request_mixed(
        self,
        rows: Dict[str, np.ndarray],
        ingest: bool = True,
        route_info: Optional[Dict] = None,
    ) -> Dict[str, Dict[str, np.ndarray]]:
        """Serve one mixed multi-scenario batch with ONE fused dispatch.

        The batch carries a per-row ``__scenario__`` tag
        (:data:`SCENARIO_COL`, set by ``ShardRouter.submit``) alongside the
        usual ``__valid__`` / ``__wait_us__`` meta columns.  Instead of
        partitioning by scenario on the host and running one store query
        per group, the whole batch enters :meth:`~repro.core.scenario.
        ScenarioPlane.query_mixed` — one fused on-device route+query
        program for all scenarios and shards — and the answer comes back
        as ``{scenario: {feature: rows}}`` with each scenario's rows in
        submission order, bit-identical to the per-group path.

        Ingest preserves the legacy stream semantics exactly: real rows
        are grouped by scenario (scenario order), each group sorted by
        (key, ts), and ingested group-by-group — the same order the
        per-group path produced.  Stats/metrics are recorded per scenario
        (each request's latency sample is its queue wait plus this fused
        batch's wall time) plus the aggregate, and ``batches`` counts ONE
        batch, reflecting the single dispatch.
        """
        if SCENARIO_COL not in rows:
            raise ValueError(
                f"request_mixed needs a {SCENARIO_COL!r} tag column "
                "(per-row scenario names; ShardRouter.submit sets it)"
            )
        tel = get_telemetry()
        t0 = tel.clock.now()
        tags = np.asarray(rows[SCENARIO_COL])
        valid = rows.get("__valid__")
        wait_us = rows.get("__wait_us__")
        data = {c: v for c, v in rows.items() if not c.startswith("__")}
        n_rows = len(next(iter(data.values())))
        vmask = (
            np.asarray(valid, bool)[:n_rows]
            if valid is not None
            else np.ones(n_rows, bool)
        )
        n_real = int(vmask.sum())
        with tel.tracer.span(
            "request", service=self.name, scenario="mixed", rows=n_real
        ):
            out = self.plane.query_mixed(
                data, tags, mode=self.mode, valid=vmask,
                route_info=route_info,
            )
            with tel.tracer.span("request.fetch"):
                out = {
                    s: {k: np.asarray(v) for k, v in cols.items()}
                    for s, cols in out.items()
                }
            if ingest and n_real:
                key_c = self.view.schema.key
                ts_c = self.view.schema.ts
                for s in self.scenarios:
                    m = vmask & (tags == s)
                    if not m.any():
                        continue
                    grp = {c: np.asarray(v)[m] for c, v in data.items()}
                    order = np.lexsort(
                        (np.asarray(grp[ts_c]), np.asarray(grp[key_c]))
                    )
                    self.store.ingest({c: v[order] for c, v in grp.items()})
        dt = tel.clock.now() - t0
        with tel.tracer.span("request.record"):
            if wait_us is not None:
                waits_s = np.asarray(wait_us, np.float64)[:n_rows] / 1e6
            else:
                waits_s = np.zeros(n_rows, np.float64)
            agg_waits = waits_s[vmask]
            req_lat = agg_waits + dt
            self._record_batch(
                tel, req_lat, agg_waits if wait_us is not None else None,
                n_real, n_rows if valid is not None else 0,
            )
            sreq = tel.metrics.counter(
                "service_requests_total", "requests served", "1",
                labels=("service", "scenario"),
            )
            self.stats.observe(dt, n_real)
            self.stats.observe_requests(req_lat)
            for s in self.scenarios:
                msk = vmask & (tags == s)
                n_s = int(msk.sum())
                if not n_s:
                    continue
                sreq.inc(n_s, service=self.name, scenario=s)
                st = self.scenario_stats[s]
                st.observe(dt, n_s)
                st.observe_requests(waits_s[msk] + dt)
        return out

    def _observe(self, latency_s, n_requests, scenario,
                 request_latencies_s=None):
        self.stats.observe(latency_s, n_requests)
        self.scenario_stats[scenario].observe(latency_s, n_requests)
        if request_latencies_s is not None:
            self.stats.observe_requests(request_latencies_s)
            self.scenario_stats[scenario].observe_requests(
                request_latencies_s
            )

    def _scenario_features(self, scenario):
        if scenario is None:
            raise ValueError("feature_matrix needs scenario= on a "
                             "multi-scenario service")
        return self.plane.views[scenario].features


class BatchScheduler:
    """Coalesce requests into fixed-shape batches (bucketed padding).

    With ``max_wait_us`` set, :meth:`next_batch` implements the real
    micro-batching deadline: it holds the queue open until either
    ``max_batch`` requests have accumulated or the *oldest* queued request
    has waited ``max_wait_us`` microseconds — whichever comes first — so a
    trickle of traffic still flushes partial batches within the latency
    budget.  Without it, any queued request flushes immediately (the
    legacy immediate-drain behaviour).

    Time is injectable (``now_us``) so schedulers are testable and
    replayable; real callers omit it and read the plane clock —
    ``repro.obs.get_telemetry().clock`` — so a :class:`repro.obs.FakeClock`
    installed via ``use_telemetry`` drives the scheduler, the registry,
    and every span from the same counter.
    """

    def __init__(
        self,
        buckets: Sequence[int] = (1, 4, 16, 64, 256),
        max_batch: Optional[int] = None,
        max_wait_us: Optional[int] = None,
    ):
        self.buckets = sorted(buckets)
        self.max_batch = max_batch
        self.max_wait_us = max_wait_us
        self.queue: List[Dict] = []
        self._arrival_us: List[int] = []
        self._injected_clock: Optional[bool] = None

    def _clock_us(self, now_us: Optional[int]) -> int:
        # a scheduler must live entirely on one clock: mixing an injected
        # test clock with the plane's monotonic clock would compare epochs
        # microseconds vs ~hours apart and either stall queued requests
        # forever or flush every batch instantly — fail loudly instead
        injected = now_us is not None
        if self._injected_clock is None:
            self._injected_clock = injected
        elif self._injected_clock != injected:
            raise ValueError(
                "BatchScheduler clock mode mixed: pass now_us on every "
                "call or on none (instance started with "
                f"{'injected' if self._injected_clock else 'monotonic'} time)"
            )
        return int(now_us) if injected else get_telemetry().clock.now_us()

    def submit(self, row: Dict, now_us: Optional[int] = None) -> None:
        self.queue.append(row)
        self._arrival_us.append(self._clock_us(now_us))

    def oldest_wait_us(self, now_us: Optional[int] = None) -> Optional[int]:
        if not self._arrival_us:
            return None
        return self._clock_us(now_us) - self._arrival_us[0]

    def ready(
        self,
        now_us: Optional[int] = None,
        flush: bool = False,
        max_batch: Optional[int] = None,
    ) -> bool:
        """Whether :meth:`next_batch` would pop a batch now: the queue
        holds a request and, under a ``max_wait_us`` deadline, is full
        (``max_batch``) or its oldest request has expired — or ``flush``
        overrides the deadline (shutdown / drain paths)."""
        if not self.queue:
            return False
        if self.max_wait_us is None or flush:
            return True
        max_batch = max_batch if max_batch is not None else self.max_batch
        full = max_batch is not None and len(self.queue) >= max_batch
        return full or self.oldest_wait_us(now_us) >= self.max_wait_us

    def next_batch(
        self,
        max_batch: Optional[int] = None,
        now_us: Optional[int] = None,
        flush: bool = False,
    ) -> Optional[Dict[str, np.ndarray]]:
        """Pop the next padded batch, or None.

        None means *empty queue* — or, under a ``max_wait_us`` deadline,
        *keep coalescing* (see :meth:`ready`).  The pop itself (row dicts
        to columns, padding, per-row waits) is the ``sched.pop`` span.
        """
        if not self.ready(now_us, flush, max_batch):
            return None
        with get_telemetry().tracer.span("sched.pop"):
            return self._pop(max_batch, now_us)

    def _pop(
        self, max_batch: Optional[int], now_us: Optional[int]
    ) -> Dict[str, np.ndarray]:
        max_batch = max_batch if max_batch is not None else self.max_batch
        n = len(self.queue)
        if max_batch:
            n = min(n, max_batch)
        bucket = next((b for b in self.buckets if b >= n), self.buckets[-1])
        n = min(n, bucket)
        pop_us = self._clock_us(now_us)
        rows, self.queue = self.queue[:n], self.queue[n:]
        arrivals, self._arrival_us = (
            self._arrival_us[:n], self._arrival_us[n:]
        )
        cols = {
            k: np.asarray([r[k] for r in rows])
            for k in rows[0]
        }
        waits = np.asarray(
            [max(pop_us - a, 0) for a in arrivals], np.int64
        )
        # pad to bucket by repeating the last row (masked out by caller)
        pad = bucket - n
        if pad:
            cols = {k: np.concatenate([v, np.repeat(v[-1:], pad, 0)])
                    for k, v in cols.items()}
            waits = np.concatenate([waits, np.repeat(waits[-1:], pad)])
        cols["__valid__"] = np.arange(bucket) < n
        cols["__wait_us__"] = waits
        m = get_telemetry().metrics
        m.counter(
            "padding_rows_total", "filler rows added to reach shape bucket",
            "1", labels=("layer",),
        ).inc(pad, layer="scheduler")
        m.gauge(
            "padding_waste_ratio", "filler rows / bucket rows, last batch",
            "1", labels=("layer",),
        ).set(pad / bucket, layer="scheduler")
        return cols


class ScoringService:
    """features -> signature embedding -> model -> score (fraud §3.3)."""

    def __init__(self, feature_service: FeatureService, model, params,
                 embed_table: jnp.ndarray, num_hashes: int = 2):
        from repro.core.signature import signature_ids
        from repro.kernels.signature.ops import signature_embed

        self.fs = feature_service
        self.model = model
        self.params = params
        self.table = embed_table
        self.num_hashes = num_hashes
        self._signature_ids = signature_ids
        self._embed = signature_embed

        cfg = model.cfg

        def score(params, feats, emb):
            # feature vector projected as frontend embeddings + a CLS token
            B = feats.shape[0]
            fe = jnp.concatenate(
                [feats[:, None, :], emb[:, None, :]], axis=1
            )
            P = cfg.frontend_len
            fe = jnp.pad(fe, ((0, 0), (0, P - 2), (0, 0)))
            batch = {
                "tokens": jnp.zeros((B, 1), jnp.int32),
                "frontend_embeds": fe,
            }
            logits, _ = model.prefill(params, batch, max_len=P + 1)
            return jax.nn.sigmoid(logits[:, -1, 0])

        self._score = jax.jit(score)

    def handle(self, rows: Dict[str, np.ndarray]) -> np.ndarray:
        feats = self.fs.feature_matrix(rows)  # (B, F)
        cfg = self.model.cfg
        F = feats.shape[1]
        pad = np.zeros((feats.shape[0], cfg.d_model - F), np.float32)
        featvec = jnp.asarray(np.concatenate([feats, pad], -1), jnp.float32)
        sig = self._signature_ids(
            [jnp.asarray(rows[self.fs.view.schema.key], jnp.int32)], bits=20
        )
        emb = self._embed(
            self.table, sig,
            jnp.ones((self.num_hashes,), jnp.float32) / self.num_hashes,
            num_hashes=self.num_hashes,
        )
        emb = jnp.pad(emb, ((0, 0), (0, cfg.d_model - emb.shape[-1])))
        return np.asarray(self._score(self.params, featvec, emb))
