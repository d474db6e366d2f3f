"""route_rank / fused-dispatch capacity boundaries (ISSUE 9 satellite).

Three edges the curated suites never hit:

* ``route_rank`` correctness at and just above the 2^20 Pallas row
  cutoff (the auto-dispatch boundary), plus interpret-mode Pallas parity
  at pow2-edge batch sizes;
* ``_route_bucket`` values and invariants at pow2 edges — the optimistic
  grid capacity is a latency guess, never a correctness one, so its
  contract (pow2, floored at 16, capped at pow2ceil(m), monotone) is
  what the overflow machinery relies on;
* the overflow → exact re-dispatch path at a pow2 edge, and the
  ≤2-compiles-per-shape-bucket budget under generated-view diversity
  (one optimistic capacity + one safe cap per batch shape, never more).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import FeatureView, ShardedOnlineStore
from repro.core.expr import Col, range_window, w_count, w_sum
from repro.data.synthetic import STRESS_DB, stress_stream
from repro.kernels.route.ops import _ROUTE_PALLAS_MAX_ROWS, route_rank
from repro.kernels.route.ref import route_rank_ref
from repro.stress.generate import NUM_ENTITIES, T_MAX, gen_views, stress_rng


def _expected_ranks(shard: np.ndarray, S: int):
    """Independent O(n) oracle: rank = #earlier rows on the same shard."""
    counts = np.bincount(shard, minlength=S)
    order = np.argsort(shard, kind="stable")
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
    rank = np.empty(len(shard), np.int64)
    rank[order] = np.arange(len(shard)) - np.repeat(starts, counts)
    return rank, counts


@pytest.mark.parametrize(
    "n", [_ROUTE_PALLAS_MAX_ROWS, _ROUTE_PALLAS_MAX_ROWS + 1]
)
def test_route_rank_at_pallas_cutoff(n):
    """Exactly at / just above the cutoff: the XLA path (what auto picks
    above the boundary, and everywhere off-TPU) stays correct at rows
    the curated batches never reach."""
    S = 8
    rng = np.random.default_rng(n)
    shard = rng.integers(0, S, size=n).astype(np.int32)
    rank, counts = route_rank(jnp.asarray(shard), num_shards=S, impl="xla")
    exp_rank, exp_counts = _expected_ranks(shard, S)
    assert np.array_equal(np.asarray(counts), exp_counts)
    assert np.array_equal(np.asarray(rank), exp_rank)
    # auto must agree bit-for-bit with the explicit impl on this backend
    rank_a, counts_a = route_rank(jnp.asarray(shard), num_shards=S)
    assert np.array_equal(np.asarray(rank_a), exp_rank)
    assert np.array_equal(np.asarray(counts_a), exp_counts)


def test_route_rank_auto_cutoff_is_tpu_only():
    """The auto policy: Pallas only on a TPU backend and only at or
    below the row cutoff — on this (CPU) backend auto resolves to the
    XLA reference for every size."""
    assert _ROUTE_PALLAS_MAX_ROWS == 1 << 19
    assert jax.default_backend() != "tpu" or pytest.skip("CPU-only check")


def test_route_rank_cutover_is_counted(monkeypatch):
    """On a TPU backend a batch past the kernel's residency cap goes to
    XLA — and says so in ``kernel_cutover_total`` (the backend is steered
    here; the XLA path then runs on this CPU)."""
    import repro.kernels.route.ops as rops
    from repro.obs import Telemetry, use_telemetry

    tel = Telemetry()
    n = _ROUTE_PALLAS_MAX_ROWS + 1
    shard = np.arange(n, dtype=np.int32) % 4
    with use_telemetry(tel):
        monkeypatch.setattr(rops.jax, "default_backend", lambda: "tpu")
        rank, counts = route_rank(jnp.asarray(shard), num_shards=4)
        monkeypatch.undo()
    snap = tel.metrics.snapshot()
    exp_rank, exp_counts = _expected_ranks(shard, 4)
    assert np.array_equal(np.asarray(rank), exp_rank)
    assert np.array_equal(np.asarray(counts), exp_counts)
    cut = snap["kernel_cutover_total"]["series"]
    assert [s["value"] for s in cut] == [1.0]
    disp = snap["kernel_dispatch_total"]["series"]
    assert {(s["labels"]["impl"], s["value"]) for s in disp} == {("xla", 1.0)}


@pytest.mark.parametrize("n", [15, 16, 17, 1023, 1024, 1025])
def test_route_rank_pallas_interpret_pow2_edges(n):
    """Interpret-mode Pallas parity at pow2-edge sizes (the tiling's
    padding boundary: lane remainder vs full tiles)."""
    S = 4
    rng = np.random.default_rng(n)
    shard = rng.integers(0, S, size=n).astype(np.int32)
    r_ref, c_ref = route_rank_ref(jnp.asarray(shard), S)
    r_pal, c_pal = route_rank(
        jnp.asarray(shard), num_shards=S, impl="pallas", interpret=True
    )
    assert np.array_equal(np.asarray(r_pal), np.asarray(r_ref))
    assert np.array_equal(np.asarray(c_pal), np.asarray(c_ref))


def _edge_view() -> FeatureView:
    return FeatureView(
        "route_edge",
        features={
            "s": w_sum(Col("amount"), range_window(256, bucket=64)),
            "c": w_count(Col("amount"), range_window(512, bucket=64)),
        },
        database=STRESS_DB,
    )


def _edge_store(num_keys=256, num_shards=8, device_routing=True):
    return ShardedOnlineStore(
        _edge_view(),
        num_keys=num_keys,
        num_shards=num_shards,
        capacity=64,
        device_routing=device_routing,
    )


def test_route_bucket_pow2_edges():
    store = _edge_store()
    S = store.num_shards
    f = store._route_bucket
    # hand-computed pow2-edge values for S=8: per-shard share doubles,
    # pow2-rounded, floored at 16, capped at pow2ceil(m)
    assert [f(m) for m in (1, 2, 8, 15, 16, 17)] == [1, 2, 8, 16, 16, 16]
    assert f(64) == 16           # even split: 8/shard, 2x=16
    assert f(65) == 32           # crossing the edge doubles the guess
    assert [f(m) for m in (128, 129, 256)] == [32, 64, 64]
    prev = 0
    for m in range(1, 1025):
        b = f(m)
        cap = 1 << max(m - 1, 0).bit_length()
        assert b & (b - 1) == 0          # power of two
        assert b <= max(cap, 1)          # never beyond the safe cap
        assert b >= min(16, cap)         # floored at 16 (unless capped)
        assert b >= prev                 # monotone in m
        prev = b


def test_overflow_redispatch_exact_at_pow2_edge():
    """An adversarial batch one row past the optimistic capacity on a
    single shard: the on-device overflow flag must re-dispatch at the
    safe cap and stay bit-identical to the host-routed oracle — and the
    shape bucket must have compiled exactly two capacities."""
    rng = np.random.default_rng(123)
    dev = _edge_store(device_routing=True)
    host = _edge_store(device_routing=False)
    n = 400
    rows = dict(
        entity=rng.integers(0, 256, n).astype(np.int32),
        ts=np.sort(rng.choice(3000, n, replace=False)).astype(np.int32),
        amount=rng.gamma(2.0, 30.0, n).astype(np.float32),
        quantity=np.ones(n, np.float32),
        score=np.zeros(n, np.float32),
        item=np.zeros(n, np.int32),
    )
    order = np.lexsort((rows["ts"], rows["entity"]))
    for s in (dev, host):
        s.ingest({c: v[order] for c, v in rows.items()})
    # pick 17 keys that all route to one shard: m=17 gets optimistic
    # bucket 16 (pow2 edge), so a one-shard batch overflows by one row
    all_keys = np.arange(256, dtype=np.int64)
    on_shard = all_keys[np.asarray(dev.shard_of(all_keys)) == 0][:17]
    assert len(on_shard) == 17
    assert dev._route_bucket(17) == 16
    m = len(on_shard)
    req = dict(
        entity=on_shard.astype(np.int32),
        ts=np.full(m, 3500, np.int32),
        amount=np.ones(m, np.float32),
        quantity=np.ones(m, np.float32),
        score=np.zeros(m, np.float32),
        item=np.zeros(m, np.int32),
    )
    a = dev.query(req, mode="preagg")
    b = host.query(req, mode="preagg")
    for f in ("s", "c"):
        np.testing.assert_array_equal(np.asarray(a[f]), np.asarray(b[f]))
    # ≤2 compiles for the shape bucket: optimistic 16 + safe cap 32
    caps = {k[2] for k in dev._fused_fns}
    assert caps == {16, 32}, caps


def test_compile_budget_under_generated_view_diversity():
    """Generated-view diversity must not widen the per-shape compile
    budget: for every (program, mode, scenario-count) group, at most two
    grid capacities — the optimistic bucket and the safe cap."""
    from repro.core.scenario import ScenarioPlane

    views = gen_views(5, 8)
    plane = ScenarioPlane(
        views, num_keys=NUM_ENTITIES, num_shards=8, name="budget",
        capacity=256, secondary_num_keys={"items": 24},
    )
    tabs = stress_stream(
        stress_rng(5, 8, "default", "data"), 600,
        num_entities=NUM_ENTITIES, num_items=24, t_max=T_MAX,
    )
    for t in plane.store._sec_names:
        sch = STRESS_DB.table(t)
        cols = tabs[t]
        order = np.lexsort((cols[sch.ts], cols[sch.key]))
        plane.ingest_table(t, {c: v[order] for c, v in cols.items()})
    ev = tabs["events"]
    order = np.lexsort((ev["ts"], ev["entity"]))
    plane.ingest({c: v[order] for c, v in ev.items()})
    rng = np.random.default_rng(17)
    scens = plane.scenarios
    for start in (0, 64, 128, 192):
        idx = np.arange(start, start + 48)
        probe = {c: v[idx] for c, v in ev.items()}
        tags = np.array([scens[i % len(scens)] for i in range(48)])
        plane.query_mixed(probe, tags)
    # adversarial one-shard batch forces the overflow capacity too
    keys = np.arange(NUM_ENTITIES, dtype=np.int64)
    skewed = keys[np.asarray(plane.store.shard_of(keys)) == 1]
    idx = np.where(np.isin(ev["entity"], skewed))[0][:48]
    if len(idx):
        probe = {c: v[idx] for c, v in ev.items()}
        tags = np.array([scens[i % len(scens)] for i in range(len(idx))])
        plane.query_mixed(probe, tags)
    by_group = {}
    for pname, mode, bucket, num_scen in plane.store._fused_fns:
        by_group.setdefault((pname, mode, num_scen), set()).add(bucket)
    assert by_group, "fused path never compiled"
    for group, buckets in by_group.items():
        assert len(buckets) <= 2, (group, buckets)


def test_overflow_redispatch_is_counted():
    """A one-key batch puts every row on one of 4 shards, past the
    optimistic bucket: the fused route re-dispatches once, counted in
    ``route_redispatch_total`` and marked on its ``route.device`` span;
    a batch spread over the shards re-dispatches nothing."""
    from repro.obs import Telemetry, use_telemetry

    tel = Telemetry()
    with use_telemetry(tel):
        store = _edge_store(num_keys=256, num_shards=4)
        n = 64
        assert store._route_bucket(n) == 32

        def req(keys):
            return dict(
                entity=keys.astype(np.int32),
                ts=np.full(n, 3500, np.int32),
                amount=np.ones(n, np.float32),
                quantity=np.ones(n, np.float32),
                score=np.zeros(n, np.float32),
                item=np.zeros(n, np.int32),
            )

        spread = np.arange(n)
        assert np.bincount(np.asarray(store.shard_of(spread))).max() <= 32
        store.query(req(spread))
        assert "route_redispatch_total" not in tel.metrics.snapshot()
        store.query(req(np.full(n, 7)))
    counter = tel.metrics.snapshot()["route_redispatch_total"]["series"]
    assert [(s["labels"], s["value"]) for s in counter] == [
        ({"program": ""}, 1.0)
    ]
    marks = [
        s.attrs["redispatched"]
        for r in tel.tracer.roots()
        for s in r.find("route.device")
    ]
    assert marks == [False, True]
