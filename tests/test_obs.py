"""Telemetry plane: clocks, spans, metric schema, and the instrumented
request path.

Everything deterministic runs under :class:`FakeClock` — one counter
drives the monotonic clock (scheduler, spans) AND the wall clock
(registry deploy stamps), which is the unified-clock contract of
satellite #2.
"""

import json

import numpy as np
import pytest

from repro.core import (
    Col,
    FeatureView,
    range_window,
    rows_window,
    w_count,
    w_first,
    w_last,
    w_mean,
    w_sum,
    w_topn_freq,
)
from repro.data.synthetic import FRAUD_SCHEMA
from repro.obs import (
    DEFAULT_BUCKETS_S,
    QUEUE_WAIT_BUCKETS_S,
    FakeClock,
    MetricCardinalityError,
    MetricRegistry,
    Telemetry,
    use_telemetry,
)
from repro.serve.router import ShardRouter
from repro.serve.service import BatchScheduler, FeatureService, ServiceStats

AMT = Col("amount")


def _row(rng, ts, num_cards=32):
    return dict(
        card=int(rng.integers(0, num_cards)),
        ts=int(ts),
        amount=float(rng.gamma(1.5, 60.0)),
        mcc=int(rng.integers(0, 32)),
        device=int(rng.integers(0, 8)),
        geo=int(rng.integers(0, 16)),
    )


# -- clock + spans -----------------------------------------------------------


def test_fake_clock_drives_monotonic_and_wall_together():
    clk = FakeClock(start_s=10.0, epoch_s=1_000.0)
    assert clk.now() == 10.0
    assert clk.now_us() == 10_000_000
    assert clk.time() == 1_010.0
    clk.tick(2_500)  # 2.5 ms in µs
    assert clk.now() == pytest.approx(10.0025)
    assert clk.time() == pytest.approx(1_010.0025)
    clk.advance(1.0)
    assert clk.now_us() == 11_002_500
    with pytest.raises(ValueError):
        clk.advance(-1.0)


def test_span_tree_deterministic_under_fake_clock():
    tel = Telemetry(clock=FakeClock())
    tr = tel.tracer
    with tr.span("request", service="svc") as root:
        tel.clock.advance(0.010)
        with tr.span("query.route"):
            tel.clock.advance(0.003)
        with tr.span("query.compute", kind="device") as sp:
            tel.clock.advance(0.005)
            sp.fence(np.float32(1.0))
        tel.clock.advance(0.002)
    assert root.duration_s == pytest.approx(0.020)
    (route,) = root.find("query.route")
    (compute,) = root.find("query.compute")
    assert route.duration_s == pytest.approx(0.003)
    assert compute.duration_s == pytest.approx(0.005)
    assert compute.fenced and compute.kind == "device"
    assert not route.fenced
    # completed spans land in the span_seconds histogram
    h = tel.metrics.histogram(
        "span_seconds", "span durations", "s", labels=("name", "kind")
    )
    assert h.count(name="request", kind="host") == 1
    assert h.sum(name="query.compute", kind="device") == pytest.approx(0.005)
    # and in the snapshot's recent-span list, as a nested dict
    snap = tel.snapshot()
    assert snap["spans"][-1]["name"] == "request"
    names = [c["name"] for c in snap["spans"][-1]["children"]]
    assert names == ["query.route", "query.compute"]


def test_disabled_telemetry_records_nothing_but_still_fences():
    tel = Telemetry(enabled=False, clock=FakeClock())
    with tel.tracer.span("request") as sp:
        out = sp.fence(np.arange(3))
    assert np.array_equal(out, np.arange(3))
    assert tel.snapshot()["metrics"] == {}
    assert tel.snapshot()["spans"] == []


def _one_chip_router(tel, max_wait_us=10_000):
    view = FeatureView(
        "tree", FRAUD_SCHEMA,
        {
            "s": w_sum(AMT, range_window(600, bucket=64)),
            "c5": w_count(AMT, rows_window(5)),
        },
    )
    with use_telemetry(tel):
        svc = FeatureService.build("tree", view, num_keys=32, capacity=64)
    return ShardRouter(
        svc, BatchScheduler(buckets=(4,), max_batch=4,
                            max_wait_us=max_wait_us),
        ingest=False,
    )


def _names(spans):
    return [s.name for s in spans]


def test_one_pump_span_tree_in_order():
    """The read path's host phases, as one one-chip pump records them;
    polls that pop nothing record no span; a write's host preparation
    precedes its fenced ingest."""
    clk = FakeClock()
    tel = Telemetry(clock=clk)
    router = _one_chip_router(tel)
    rng = np.random.default_rng(3)
    with use_telemetry(tel):
        for i in range(3):
            router.submit(_row(rng, 1_000 + i), now_us=0)
        # neither full (4) nor expired (10 ms): keep coalescing
        assert router.pump(now_us=5_000) is None
        assert router.pump(now_us=9_999) is None
        assert tel.tracer.roots() == []
        clk.advance(0.010)
        out = router.pump(now_us=10_000)
        assert len(out["s"]) == 3
        assert router.pump(now_us=20_000) is None  # queue empty
    (pump,) = tel.tracer.roots()
    assert pump.name == "router.pump"
    assert pump.attrs == {"rows": 3, "padded": 4}
    assert tel.metrics.metrics()["queue_wait_seconds"].bounds == (
        QUEUE_WAIT_BUCKETS_S
    )
    assert _names(pump.children) == ["sched.pop", "request", "request.record"]
    (request,) = pump.find("request")
    assert _names(request.children) == [
        "query.prepare", "query.compute", "query.finish", "request.fetch",
    ]
    (compute,) = pump.find("query.compute")
    assert compute.kind == "device" and compute.fenced
    host = [s for s in pump.children + request.children if s is not compute]
    assert all(s.kind == "host" for s in host)
    with use_telemetry(tel):
        cols = {k: np.asarray([v]) for k, v in _row(rng, 2_000).items()}
        router.service.store.ingest(cols)
    assert _names(tel.tracer.roots()[1:]) == ["ingest.prepare", "ingest"]


def test_spans_reach_the_profiler_trace(tmp_path):
    """Every span is also a profiler annotation: in a CPU trace of two
    pumps under an outer annotation, each ``router.pump`` lies inside the
    outer one and each ``query.compute`` inside a ``router.pump``, on one
    host timeline."""
    import glob

    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    tel = Telemetry()
    router = _one_chip_router(tel, max_wait_us=None)
    rng = np.random.default_rng(4)
    with use_telemetry(tel):
        router.submit(_row(rng, 1_000), now_us=0)
        router.pump(now_us=0)  # compile outside the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with TraceAnnotation("outer"):
                for i in range(2):
                    router.submit(_row(rng, 1_001 + i), now_us=0)
                    assert router.pump(now_us=0) is not None
        finally:
            jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = [
        [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in ln.events]
        for plane in ProfileData.from_file(path).planes
        for ln in plane.lines
    ]
    (line,) = [ln for ln in lines if any(e[0] == "outer" for e in ln)]

    def named(name):
        return [(a, b) for n, a, b in line if n == name]

    (outer,) = named("outer")
    pumps = named("router.pump")
    computes = named("query.compute")
    assert len(pumps) == 2 and len(computes) == 2
    for a, b in pumps:
        assert outer[0] <= a <= b <= outer[1]
    for a, b in computes:
        assert any(pa <= a <= b <= pb for pa, pb in pumps)


def _scan_index(bounds, v):
    """The bucket a linear scan picks: the first bound >= v, else the
    overflow bucket."""
    i = 0
    for b in bounds:
        if v <= b:
            break
        i += 1
    return i


@pytest.mark.parametrize(
    "bounds", [DEFAULT_BUCKETS_S, QUEUE_WAIT_BUCKETS_S],
    ids=["default", "queue_wait"],
)
def test_histogram_bucket_matches_the_linear_scan(bounds):
    edges = list(bounds)
    values = (
        edges
        + [np.nextafter(b, np.inf) for b in edges]
        + [np.nextafter(b, -np.inf) for b in edges]
        + [(a + b) / 2 for a, b in zip(edges, edges[1:])]
        + [-1.0, 0.0, edges[0] / 2, edges[-1] * 1.5, edges[-1] * 1e6]
    )
    for v in values:
        h = MetricRegistry().histogram("h_seconds", bounds=bounds)
        h.observe(float(v))
        counts = [c for _, c in h.snapshot()["series"][0]["buckets"]]
        assert counts.index(1.0) == _scan_index(bounds, float(v)), v
        assert sum(counts) == 1.0


def test_queue_wait_histogram_has_ten_bounds_per_decade():
    b = np.asarray(QUEUE_WAIT_BUCKETS_S)
    assert len(b) == 51
    assert b[0] == pytest.approx(1e-4) and b[-1] == pytest.approx(10.0)
    np.testing.assert_allclose(b[1:] / b[:-1], 10 ** 0.1)


def test_unified_clock_spans_scheduler_and_registry():
    """One FakeClock advances spans, scheduler waits, and deploy stamps."""
    from repro.core.view import FeatureRegistry

    clk = FakeClock(start_s=5.0, epoch_s=2_000.0)
    tel = Telemetry(clock=clk)
    with use_telemetry(tel):
        reg = FeatureRegistry()  # no clock arg: reads the plane clock
        view = FeatureView(
            "clk", FRAUD_SCHEMA, {"s": w_sum(AMT, range_window(600))}
        )
        reg.register(view)
        rec = reg.deploy("svc", "clk")
        assert rec["deployed_at"] == 2_005.0  # epoch + elapsed monotonic
        sched = BatchScheduler(max_batch=4, max_wait_us=10_000)
        sched.submit({"card": 1, "ts": 1})  # arrival at clk.now_us()
        clk.tick(3_000)
        batch = sched.next_batch(flush=True)
        assert list(batch["__wait_us__"]) == [3_000]


# -- metric registry schema --------------------------------------------------


def test_registry_rejects_schema_drift_and_label_mismatch():
    tel = Telemetry()
    m = tel.metrics
    c = m.counter("reqs", "requests", "1", labels=("svc",))
    c.inc(svc="a")
    assert m.counter("reqs", "requests", "1", labels=("svc",)) is c
    with pytest.raises(ValueError):
        m.gauge("reqs", "requests", "1", labels=("svc",))  # type flip
    with pytest.raises(ValueError):
        m.counter("reqs", "requests", "s", labels=("svc",))  # unit flip
    with pytest.raises(ValueError):
        m.counter("reqs", "requests", "1", labels=("svc", "x"))  # labels
    with pytest.raises(ValueError):
        c.inc(other="a")  # undeclared label name


def test_metric_cardinality_cap():
    tel = Telemetry()
    c = tel.metrics.counter(
        "cardinality", "x", "1", labels=("k",), max_series=8
    )
    for i in range(8):
        c.inc(k=str(i))
    with pytest.raises(MetricCardinalityError):
        c.inc(k="overflow")
    assert c.series_count() == 8


def test_snapshot_schema_stable_and_json_round_trips():
    tel = Telemetry(clock=FakeClock())
    tel.metrics.counter("a_total", "a", "1", labels=("l",)).inc(2, l="x")
    tel.metrics.gauge("g", "g", "1", labels=()).set(0.5)
    h = tel.metrics.histogram("h_seconds", "h", "s", labels=())
    h.observe(0.010, n=3)
    snap = json.loads(tel.snapshot_json())
    assert set(snap) == {
        "schema_version", "enabled", "time_s", "metrics", "spans"
    }
    assert snap["schema_version"] == Telemetry.SCHEMA_VERSION
    for name, m in snap["metrics"].items():
        assert set(m) == {"type", "unit", "help", "labels", "series"}, name
    hs = snap["metrics"]["h_seconds"]["series"][0]
    assert hs["count"] == 3 and hs["sum"] == pytest.approx(0.030)
    for k in ("p50", "p95", "p99", "max", "buckets"):
        assert k in hs
    prom = tel.to_prometheus()
    assert 'a_total{l="x"} 2' in prom
    assert "# TYPE h_seconds histogram" in prom
    assert 'le="+Inf"' in prom


def test_golden_catalog_gate_runs():
    from repro.obs.check import schema_check

    schema_check(verbose=False)


# -- request-path semantics --------------------------------------------------


def test_request_percentiles_weight_by_request_not_batch():
    """Satellite 1: one 99-row batch + one 1-row straggler.  Batch
    percentiles say p50 = midpoint of two batches; request percentiles
    must say p50 = the big batch's latency."""
    st = ServiceStats()
    st.observe(0.010, 99)  # fast big batch
    st.observe(0.500, 1)  # slow straggler
    # deprecated batch semantics: midpoint of {10ms, 500ms}
    assert st.p50_ms == pytest.approx(255.0)
    st.observe_requests([0.010] * 99 + [0.500])
    assert st.request_p50_ms == pytest.approx(10.0)
    assert st.request_p99_ms >= 10.0
    assert st.requests == 100


def test_request_latency_includes_queue_wait():
    clk = FakeClock()
    tel = Telemetry(clock=clk)
    view = FeatureView(
        "lat", FRAUD_SCHEMA, {"s": w_sum(AMT, range_window(600, bucket=64))}
    )
    with use_telemetry(tel):
        svc = FeatureService.build("lat", view, num_keys=32, capacity=64)
        sched = BatchScheduler(max_batch=8, max_wait_us=50_000)
        rng = np.random.default_rng(0)
        sched.submit(_row(rng, 1_000))
        clk.tick(40_000)  # 40 ms in queue
        batch = sched.next_batch(flush=True)
        svc.request(batch)
    # FakeClock doesn't advance during request -> latency == queue wait
    assert svc.stats.request_p50_ms == pytest.approx(40.0)
    h = tel.metrics.histogram(
        "queue_wait_seconds", "", "s", labels=("service",)
    )
    assert h.mean(service="lat") == pytest.approx(0.040)


def test_preagg_hit_and_fallback_counters():
    """A range-window SUM is answered from the bucket pre-agg store; a
    rows-window COUNT must fall back to the raw ring fold."""
    tel = Telemetry()
    view = FeatureView(
        "pa", FRAUD_SCHEMA,
        {
            "s": w_sum(AMT, range_window(600, bucket=64)),  # hit
            "c5": w_count(AMT, rows_window(5)),  # fallback
        },
    )
    with use_telemetry(tel):
        svc = FeatureService.build("pa", view, num_keys=32, capacity=64)
        svc.request(
            {
                "card": np.arange(4, dtype=np.int32),
                "ts": np.full(4, 10_000),
                "amount": np.ones(4, np.float32),
                "mcc": np.zeros(4, np.int64),
                "device": np.zeros(4, np.int64),
                "geo": np.zeros(4, np.int64),
            }
        )
    hits = tel.metrics.counter("preagg_hits_total", "", "1", labels=("agg",))
    falls = tel.metrics.counter(
        "preagg_fallback_total", "", "1", labels=("agg",)
    )
    assert hits.value(agg="sum") == 1
    assert falls.value(agg="count") == 1
    assert hits.value(agg="count") == 0


def test_first_topn_preagg_hit_not_fallback():
    """FIRST/LAST/TOPN over range windows compose from the merge-order
    bucket families — the pre-agg path answers them with ZERO fallbacks
    (the counter this used to light up)."""
    tel = Telemetry()
    view = FeatureView(
        "mo", FRAUD_SCHEMA,
        {
            "f": w_first(AMT, range_window(600, bucket=64)),
            "l": w_last(AMT, range_window(600, bucket=64)),
            "t0": w_topn_freq(Col("mcc"), range_window(600, bucket=64), n=0),
        },
    )
    with use_telemetry(tel):
        svc = FeatureService.build("mo", view, num_keys=32, capacity=64)
        svc.request(
            {
                "card": np.arange(4, dtype=np.int32),
                "ts": np.full(4, 10_000),
                "amount": np.ones(4, np.float32),
                "mcc": np.zeros(4, np.int64),
                "device": np.zeros(4, np.int64),
                "geo": np.zeros(4, np.int64),
            }
        )
    hits = tel.metrics.counter("preagg_hits_total", "", "1", labels=("agg",))
    falls = tel.metrics.counter(
        "preagg_fallback_total", "", "1", labels=("agg",)
    )
    for agg in ("first", "last", "topn_freq"):
        assert hits.value(agg=agg) == 1, agg
        assert falls.value(agg=agg) == 0, agg
    # every ingest dispatch is counted by resolved implementation; the
    # merge-order families route ingest down the split XLA path on any
    # backend (the fused kernel covers only the six core arrays)
    kd = tel.metrics.counter(
        "kernel_dispatch_total", "", "1", labels=("kernel", "impl")
    )
    assert kd.value(kernel="fused_ingest", impl="xla") >= 1


def test_compile_time_captured_once_per_trace():
    tel = Telemetry()
    view = FeatureView(
        "ct", FRAUD_SCHEMA, {"m": w_mean(AMT, range_window(600, bucket=64))}
    )
    with use_telemetry(tel):
        svc = FeatureService.build("ct", view, num_keys=32, capacity=64)
        b = {
            "card": np.arange(4, dtype=np.int32),
            "ts": np.full(4, 10_000),
            "amount": np.ones(4, np.float32),
            "mcc": np.zeros(4, np.int64),
            "device": np.zeros(4, np.int64),
            "geo": np.zeros(4, np.int64),
        }
        svc.request(b, ingest=False)
        svc.request(b, ingest=False)  # warm: same shape, no new trace
    h = tel.metrics.histogram(
        "query_compile_seconds", "", "s", labels=("program", "mode")
    )
    assert h.count(program="ct", mode="preagg") == 1
    assert h.sum(program="ct", mode="preagg") > 0


def test_overhead_within_bound():
    from repro.obs.check import overhead_check

    # generous bound at test size: the gate's real tuning lives in CI
    overhead_check(bound_ratio=4.0, floor_s=10e-3, iters=15, verbose=False)


# -- router padding / skew ---------------------------------------------------


def test_skew_histograms_exclude_padding():
    """Satellite 6: non-bucket-aligned submit counts pad every popped
    batch; the skew histograms must still sum to exactly the real
    request count, with padding reported by the telemetry instead."""
    tel = Telemetry()
    view = FeatureView(
        "skew", FRAUD_SCHEMA, {"s": w_sum(AMT, range_window(600, bucket=64))}
    )
    n_req = 13  # 13 -> buckets pad to 16 (and shard buckets pad more)
    with use_telemetry(tel):
        svc = FeatureService.build(
            "skew", view, num_keys=32, sharded=True, num_shards=4,
            capacity=64,
        )
        router = ShardRouter(
            svc, BatchScheduler(buckets=(1, 4, 16), max_batch=16)
        )
        rng = np.random.default_rng(1)
        now = 0
        for i in range(n_req):
            router.submit(_row(rng, 1_000 + i), now_us=now)
            now += 100
        router.drain(now_us=now)
    hist = router.shard_histogram()
    assert hist.sum() == n_req
    pad = tel.metrics.counter("padding_rows_total", "", "1", labels=("layer",))
    assert pad.value(layer="scheduler") == 3  # 13 padded to 16
    assert pad.value(layer="shard") > 0
    disp = tel.metrics.counter(
        "shard_dispatch_rows_total", "", "1", labels=("scenario", "shard")
    )
    assert disp.total() == n_req


def test_multi_scenario_skew_histograms_exclude_padding():
    tel = Telemetry()
    v1 = FeatureView(
        "fraud", FRAUD_SCHEMA, {"s": w_sum(AMT, range_window(600, bucket=64))}
    )
    v2 = FeatureView("risk", FRAUD_SCHEMA, {"c": w_count(AMT, rows_window(5))})
    n_req = 11
    with use_telemetry(tel):
        svc = FeatureService.build_multi(
            "ms", [v1, v2], num_keys=32, sharded=True, num_shards=4,
            capacity=64,
        )
        router = ShardRouter(
            svc, BatchScheduler(buckets=(1, 4, 16), max_batch=16)
        )
        rng = np.random.default_rng(2)
        for i in range(n_req):
            router.submit(
                _row(rng, 1_000 + i), now_us=i * 100,
                scenario="fraud" if i % 2 else "risk",
            )
        router.drain(now_us=n_req * 100)
    per = router.scenario_shard_histogram()
    assert sum(h.sum() for h in per.values()) == n_req
    assert router.shard_histogram().sum() == n_req
    assert per["fraud"].sum() == n_req // 2
    assert per["risk"].sum() == n_req - n_req // 2
