"""Sharded serving front-end — routing + micro-batching over shard meshes.

The serving plane's request dataflow (FeatInsight's online engine, scaled
out the way OpenMLDB partitions online table state across nodes):

    submit(row) ──> BatchScheduler          (coalesce: max_batch / max_wait_us)
        │
        ▼ next_batch()  — padded shape bucket + __valid__ mask
    FeatureService.request / request_mixed
        │
        ▼ ShardedOnlineStore.query          (one fused program on the mesh)
        │     device (default, ``device_routing=True``): shard =
        │     feistel(key) % S, rank-within-shard (Pallas route kernel on
        │     TPU), scatter into per-shard grids, vmapped per-shard query,
        │     gather back to request order — ALL inside one jit program;
        │     the host sees one dispatch and one transfer per batch.
        │     host (``device_routing=False`` oracle): bucket rows by shard
        │     on the host, pad per shard, device_put with
        │     NamedSharding('shard'), query, scatter back on the host.
        ▼
    per-request feature rows (submission order)

:class:`ShardRouter` owns that loop and the serving-side observability:
per-shard request occupancy (skew monitoring) and the service's latency
percentiles.  The histograms are fed by the store's own routing counts
(``route_info``) — the router never re-hashes keys.  It is
store-agnostic — a single-device store degrades to S=1 — so services opt
into sharding purely via ``FeatureService.build(..., sharded=True)``.

**Multi-scenario routing** (``FeatureService.build_multi``): requests are
submitted with a scenario tag and coalesce in ONE queue.  With device
routing the whole mixed batch goes through
:meth:`~repro.serve.service.MultiScenarioService.request_mixed` — ONE
fused dispatch answers every (scenario, shard) bucket, and per-scenario
rows come back in submission order.  With the host oracle each popped
batch is partitioned by scenario on the host and every group runs its own
program (the legacy per-group path, bit-identical).  Occupancy is tracked
per (scenario, shard) in :meth:`ShardRouter.scenario_shard_histogram`
under both flavours.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from repro.obs import get_telemetry
from repro.serve.service import (
    SCENARIO_COL,
    BatchScheduler,
    FeatureService,
    MultiScenarioService,
)

__all__ = ["ShardRouter"]

_SCENARIO_COL = SCENARIO_COL


class ShardRouter:
    """Micro-batching front-end for a (sharded, multi-scenario) service.

    ``pump()`` moves one batch through the pipeline; ``drain()`` pumps
    until the queue is empty (flushing any open coalescing window).
    Responses come back as per-request feature rows in submission order —
    for a multi-scenario service, per scenario:
    ``{scenario: {feature: rows-in-submission-order}}``.
    """

    def __init__(
        self,
        service: FeatureService,
        scheduler: Optional[BatchScheduler] = None,
        ingest: bool = True,
    ):
        self.service = service
        self.scheduler = scheduler if scheduler is not None else BatchScheduler()
        self.ingest = ingest
        self.num_shards = int(getattr(service.store, "num_shards", 1))
        self.scenarios: Optional[List[str]] = (
            list(service.scenarios)
            if isinstance(service, MultiScenarioService)
            else None
        )
        # per-shard request counts — the serving-skew histogram (aggregate
        # over scenarios), plus the per-(scenario, shard) breakdown for
        # multi-scenario deployments
        self.shard_requests = np.zeros(self.num_shards, np.int64)
        self.scenario_shard_requests: Dict[str, np.ndarray] = {
            s: np.zeros(self.num_shards, np.int64)
            for s in (self.scenarios or ())
        }

    def _sync_scenarios(self) -> None:
        """Pick up scenarios hot-deployed onto the service since this
        router was built (``MultiScenarioService.hot_deploy``): the
        scenario list and its per-(scenario, shard) histograms follow the
        live plane."""
        if self.scenarios is None:
            return
        live = list(self.service.scenarios)
        if live != self.scenarios:
            self.scenarios = live
            for s in live:
                self.scenario_shard_requests.setdefault(
                    s, np.zeros(self.num_shards, np.int64)
                )

    def submit(
        self,
        row: Dict,
        now_us: Optional[int] = None,
        scenario: Optional[str] = None,
    ) -> None:
        """Queue one request row; multi-scenario services require the
        ``scenario`` tag (which view answers this row)."""
        self._sync_scenarios()
        if self.scenarios is not None:
            if scenario is None:
                raise ValueError(
                    "multi-scenario router: submit(..., scenario=) required "
                    f"(one of {self.scenarios})"
                )
            if scenario not in self.scenario_shard_requests:
                raise KeyError(
                    f"unknown scenario {scenario!r}; service has "
                    f"{self.scenarios}"
                )
            row = dict(row)
            row[_SCENARIO_COL] = scenario
        elif scenario is not None:
            raise ValueError(
                f"service {self.service.name!r} is single-scenario; "
                "submit() takes no scenario tag"
            )
        self.scheduler.submit(row, now_us=now_us)

    def _note_route(
        self, counts: np.ndarray, scenario: Optional[str]
    ) -> None:
        """Fold one batch's routed-row counts into the skew histograms.

        ``counts`` is the per-shard histogram the store computed WHILE
        routing (``route_info["shard_counts"]`` /
        ``["scenario_shard_counts"]``), so the router never re-hashes keys
        to learn where rows went.  Padding is already excluded: the store
        masks filler rows before counting, so the histograms count real
        requests only and the plane's padding cost stays in the
        ``padding_rows_total`` / ``padding_waste_ratio`` telemetry.  The
        per-(scenario, shard) dispatch counter is one vectorized
        ``inc_along`` update, not a per-shard ``inc`` loop.
        """
        hist = np.zeros(self.num_shards, np.int64)
        counts = np.asarray(counts, np.int64)
        hist[: len(counts)] += counts
        self.shard_requests += hist
        if scenario is not None:
            self.scenario_shard_requests[scenario] += hist
        get_telemetry().metrics.counter(
            "shard_dispatch_rows_total",
            "request rows dispatched per (scenario, shard)", "1",
            labels=("scenario", "shard"),
            max_series=1024,
        ).inc_along(
            "shard",
            [str(i) for i in range(self.num_shards)],
            hist,
            scenario=scenario or "",
        )

    def pump(
        self, now_us: Optional[int] = None, flush: bool = False
    ) -> Optional[Dict[str, np.ndarray]]:
        """Serve one coalesced batch; None if nothing is ready yet.

        A pump that pops a batch is the ``router.pump`` span (the pop,
        the request, the routing histograms); a poll that finds nothing
        ready records nothing."""
        self._sync_scenarios()
        if not self.scheduler.ready(now_us=now_us, flush=flush):
            return None
        with get_telemetry().tracer.span("router.pump") as sp:
            batch = self.scheduler.next_batch(now_us=now_us, flush=flush)
            valid = np.asarray(batch["__valid__"], bool)
            sp.set(rows=int(valid.sum()), padded=len(valid))
            return self._serve(batch, valid)

    def _serve(self, batch: Dict[str, np.ndarray], valid: np.ndarray):
        if self.scenarios is None:
            ri: Dict = {}
            out = self.service.request(
                batch, ingest=self.ingest, route_info=ri
            )
            self._note_route(ri["shard_counts"], None)
            return {k: v[valid] for k, v in out.items()}
        if getattr(self.service.store, "device_routing", False):
            # device routing: the mixed batch is ONE fused dispatch — the
            # store routes, answers, and histograms every (scenario,
            # shard) bucket inside a single jit program
            ri = {}
            results = self.service.request_mixed(
                batch, ingest=self.ingest, route_info=ri
            )
            scounts = np.asarray(ri["scenario_shard_counts"])
            for i, s in enumerate(ri["scenario_names"]):
                self._note_route(scounts[i], s)
            return results
        # host oracle: partition the popped batch by scenario tag (in
        # submission order within each group) and run each group through
        # its own program — the (scenario, shard) bucketing of the plane.
        # Ingest is deferred until EVERY group is answered so the whole
        # batch is served as-of batch start, exactly the point-in-time
        # semantics the fused dispatch has (one program cannot interleave
        # per-group ingest into its own answers) — without the deferral
        # a later group would see an earlier group's rows from the same
        # batch and the two flavours could not be bit-identical.
        tags = np.asarray(batch[_SCENARIO_COL])
        results = {}
        groups = []
        for s in self.scenarios:
            m = valid & (tags == s)
            if not m.any():
                continue
            rows_s = {
                c: np.asarray(v)[m]
                for c, v in batch.items()
                if c not in ("__valid__", _SCENARIO_COL)
            }
            ri = {}
            out = self.service.request(
                rows_s, ingest=False, scenario=s, route_info=ri
            )
            # rows_s was masked by `m`, so every row is a real request
            self._note_route(ri["shard_counts"], s)
            results[s] = out
            groups.append(rows_s)
        if self.ingest:
            schema = self.service.view.schema
            for rows_s in groups:
                data = {
                    c: np.asarray(v)
                    for c, v in rows_s.items()
                    if not c.startswith("__")
                }
                order = np.lexsort((data[schema.ts], data[schema.key]))
                self.service.store.ingest(
                    {c: v[order] for c, v in data.items()}
                )
        return results

    def drain(
        self, now_us: Optional[int] = None
    ) -> Optional[Dict[str, np.ndarray]]:
        """Flush everything queued; concatenated rows in submission order
        (per scenario, for a multi-scenario service)."""
        outs: List[Dict] = []
        while True:
            got = self.pump(now_us=now_us, flush=True)
            if got is None:
                break
            outs.append(got)
        if not outs:
            return None
        if self.scenarios is None:
            return {
                k: np.concatenate([o[k] for o in outs]) for k in outs[0]
            }
        # collect every pump's per-scenario chunks first, concatenate each
        # scenario ONCE at the end — pumps arrive in submission order, so
        # chunk order is row order and a single concat per (scenario,
        # feature) preserves it without O(pumps) repeated reallocation
        merged: Dict[str, Dict[str, List[np.ndarray]]] = {}
        for o in outs:
            for s, cols in o.items():
                dst = merged.setdefault(s, {})
                for k, v in cols.items():
                    dst.setdefault(k, []).append(v)
        return {
            s: {k: np.concatenate(vs) for k, vs in cols.items()}
            for s, cols in merged.items()
        }

    def shard_histogram(self) -> np.ndarray:
        """Requests served per shard, summed over scenarios (copy).

        Counts real requests only — padded filler rows are excluded (see
        :meth:`_count_shards`); padding cost is the
        ``padding_rows_total``/``padding_waste_ratio`` telemetry.
        """
        return self.shard_requests.copy()

    def scenario_shard_histogram(self) -> Dict[str, np.ndarray]:
        """Per-(scenario, shard) request occupancy (copies); real requests
        only, padding excluded as in :meth:`shard_histogram`."""
        return {
            s: h.copy() for s, h in self.scenario_shard_requests.items()
        }
