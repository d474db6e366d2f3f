"""Algebraic property tests for the unified aggregator registry.

Every ``Agg``'s spec must be a genuine monoid — ``combine`` associative,
``init`` the identity — because every layer (offline scan, online naive,
online pre-agg, WINDOW UNION, sharded plane) evaluates folds of it in a
different association order.  Checked as hypothesis property tests where
hypothesis is installed, and as a deterministic seeded sweep everywhere
(the container may not ship hypothesis; the property still runs in tier-1).

Plus the end-to-end payoff of the algebra: FIRST and TOPN_FREQ — the two
aggregates that used to be rejected over WINDOW UNION — now agree *exactly*
between the offline engine, the online store (both query paths), and the
sharded plane.
"""

import numpy as np
import pytest

from repro.core import (
    Col,
    Database,
    FeatureView,
    TableSchema,
    range_window,
    w_first,
    w_last,
    w_sum,
    w_topn_freq,
)
from repro.core.aggregates import (
    AGG_SPECS,
    TOPN_TAIL,
    _sort_tail_desc,
    agg_spec,
)
from repro.core.consistency import verify_view
from repro.core.preagg import bucket_to_host
from repro.core.expr import UNION_AGGS, Agg

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as hst

    HAVE_HYPOTHESIS = True
except ImportError:  # container may not ship hypothesis
    HAVE_HYPOTHESIS = False

B = 5  # batch shape of generated states — combines are elementwise-batched


# ---------------------------------------------------------------------------
# state generators / observational equality
# ---------------------------------------------------------------------------


def _random_states(spec, rng, count):
    """``count`` random valid states of ``spec``, batch shape (B,).

    Lane values are integer-valued floats so f32 addition is exact (the
    associativity contract is algebraic; fp rounding is tested by the
    consistency suite's tolerances instead).  Merge coordinates (ts, rank,
    pos) are globally distinct — the merge order is a strict total order
    over real rows, so equal coordinates cannot occur.
    """
    if spec.state == "lanes":
        return [
            {
                l: rng.integers(-50, 50, B).astype(np.float32)
                for l in spec.lanes
            }
            for _ in range(count)
        ]
    if spec.state == "bitmap":
        return [
            {"bits": rng.integers(0, 2**31 - 1, B).astype(np.int32)}
            for _ in range(count)
        ]
    if spec.state == "extreme":
        ts = rng.choice(10**6, size=(count, B), replace=False)
        return [
            {
                "ts": ts[i].astype(np.int32),
                "rank": rng.integers(0, 4, B).astype(np.int32),
                "pos": rng.integers(0, 256, B).astype(np.int32),
                "val": rng.integers(-50, 50, B).astype(np.float32),
                "has": rng.random(B) < 0.8,
            }
            for i in range(count)
        ]
    # tail: canonical states (entries newest-first, valid-first)
    widths = rng.integers(0, 13, count)
    total = int(widths.sum())
    ts_pool = rng.choice(10**6, size=(total, B), replace=False)
    out, used = [], 0
    for w in widths:
        w = int(w)
        s = {
            "ts": ts_pool[used:used + w].T.astype(np.int32),
            "rank": rng.integers(0, 4, (B, w)).astype(np.int32),
            "pos": rng.integers(0, 256, (B, w)).astype(np.int32),
            "val": rng.integers(-8, 8, (B, w)).astype(np.float32),
            "valid": np.ones((B, w), bool),
        }
        used += w
        out.append({k: np.asarray(v) for k, v in _sort_tail_desc(
            {k: np.asarray(v) for k, v in s.items()}
        ).items()})
    return out


def _states_equal(spec, a, b):
    """Observational state equality (fields of absent/invalid entries are
    don't-cares)."""
    a = {k: np.asarray(v) for k, v in a.items()}
    b = {k: np.asarray(v) for k, v in b.items()}
    if spec.state in ("lanes", "bitmap"):
        return all(np.array_equal(a[k], b[k]) for k in a)
    if spec.state == "extreme":
        if not np.array_equal(a["has"], b["has"]):
            return False
        h = a["has"]
        return all(
            np.array_equal(a[k][h], b[k][h])
            for k in ("ts", "rank", "pos", "val")
        )
    if a["valid"].shape != b["valid"].shape or not np.array_equal(
        a["valid"], b["valid"]
    ):
        return False
    v = a["valid"]
    return all(
        np.array_equal(a[k][v], b[k][v]) for k in ("ts", "rank", "pos", "val")
    )


def _check_associative(agg, seed):
    spec = agg_spec(agg)
    sa, sb, sc = _random_states(spec, np.random.default_rng(seed), 3)
    left = spec.combine(spec.combine(sa, sb), sc)
    right = spec.combine(sa, spec.combine(sb, sc))
    assert _states_equal(spec, left, right), (
        f"{agg}: combine not associative (seed {seed})"
    )


def _check_identity(agg, seed):
    spec = agg_spec(agg)
    (s,) = _random_states(spec, np.random.default_rng(seed), 1)
    ident = spec.init((B,))
    assert _states_equal(spec, spec.combine(ident, s), s), (
        f"{agg}: init is not a left identity (seed {seed})"
    )
    assert _states_equal(spec, spec.combine(s, ident), s), (
        f"{agg}: init is not a right identity (seed {seed})"
    )


# ---------------------------------------------------------------------------
# the properties — deterministic sweep (always) + hypothesis (where present)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("agg", list(Agg))
@pytest.mark.parametrize("seed", range(6))
def test_combine_associative(agg, seed):
    _check_associative(agg, 1000 * seed + 17)


@pytest.mark.parametrize("agg", list(Agg))
@pytest.mark.parametrize("seed", range(6))
def test_init_identity(agg, seed):
    _check_identity(agg, 1000 * seed + 29)


if HAVE_HYPOTHESIS:

    @settings(
        max_examples=80, deadline=None,
        suppress_health_check=[HealthCheck.too_slow],
    )
    @given(
        agg=hst.sampled_from(list(Agg)), seed=hst.integers(0, 2**20)
    )
    def test_combine_associative_hypothesis(agg, seed):
        _check_associative(agg, seed)

    @settings(
        max_examples=80, deadline=None,
        suppress_health_check=[HealthCheck.too_slow],
    )
    @given(
        agg=hst.sampled_from(list(Agg)), seed=hst.integers(0, 2**20)
    )
    def test_init_identity_hypothesis(agg, seed):
        _check_identity(agg, seed)


def test_registry_covers_every_agg_and_union_flags_match():
    assert set(AGG_SPECS) == set(Agg)
    assert tuple(a for a in Agg if AGG_SPECS[a].union_composable) == tuple(
        sorted(UNION_AGGS, key=list(Agg).index)
    )
    # every state family is bucket-composable: lanes/bitmap persist in the
    # core stat arrays, extreme/tail in the merge-order state arrays the
    # layout plans alongside (BucketPlan.extreme / .tail)
    for agg, spec in AGG_SPECS.items():
        assert spec.bucket_composable, agg
        assert spec.state in ("lanes", "bitmap", "extreme", "tail"), agg


# ---------------------------------------------------------------------------
# end-to-end: FIRST / TOPN_FREQ under WINDOW UNION, exact on every path
# ---------------------------------------------------------------------------

DB = Database(
    name="alg",
    primary=TableSchema("tx", key="acct", ts="ts", numeric=("amount",)),
    secondary=(
        TableSchema("wires", key="acct", ts="ts", numeric=("amount",)),
    ),
)


def _union_workload(seed, n=260, m=130, k=7, t_max=2_000):
    rng = np.random.default_rng(seed)
    # unique timestamps across both tables: the merge order is then fully
    # determined by ts, so brute-force/offline/online agree unambiguously
    all_ts = rng.choice(t_max, size=n + m, replace=False).astype(np.int32)
    tx = dict(
        acct=rng.integers(0, k, n).astype(np.int32),
        ts=np.sort(all_ts[:n]),
        amount=rng.integers(0, 6, n).astype(np.float32),
    )
    wires = dict(
        acct=rng.integers(0, k, m).astype(np.int32),
        ts=np.sort(all_ts[n:]),
        amount=rng.integers(0, 6, m).astype(np.float32),
    )
    return tx, wires, k


UNION_VIEW = FeatureView(
    "union_exact", DB.primary, {
        "first_u": w_first(
            Col("amount"), range_window(500, bucket=64), union=("wires",)
        ),
        "last_u": w_last(
            Col("amount"), range_window(500, bucket=64), union=("wires",)
        ),
        "top1_u": w_topn_freq(
            Col("amount"), range_window(400, bucket=64), n=0, union=("wires",)
        ),
        "top2_u": w_topn_freq(
            Col("amount"), range_window(400, bucket=64), n=1, union=("wires",)
        ),
    },
    database=DB,
)


@pytest.mark.parametrize("mode", ["naive", "preagg"])
@pytest.mark.parametrize("num_shards", [None, 4])
def test_first_topn_union_exact(mode, num_shards):
    tx, wires, k = _union_workload(seed=23)
    rep = verify_view(
        UNION_VIEW, tx, num_keys=k, capacity=256, num_buckets=64,
        bucket_size=64, mode=mode, secondary={"wires": wires},
        num_shards=num_shards,
    )
    assert rep.passed, rep.summary() + f" per-feature: {rep.per_feature}"
    # FIRST/LAST/TOPN return raw row values — no fp accumulation, so the
    # offline/online/sharded agreement must be *exact*, not tolerance-based
    for f, err in rep.per_feature.items():
        assert err == 0.0, f"{f}: max abs err {err} (expected exact)"


PRIMARY_VIEW = FeatureView(
    "primary_exact", DB.primary, {
        "first_r": w_first(Col("amount"), range_window(500, bucket=64)),
        "last_r": w_last(Col("amount"), range_window(500, bucket=64)),
        "top1_r": w_topn_freq(
            Col("amount"), range_window(400, bucket=64), n=0
        ),
        "top2_r": w_topn_freq(
            Col("amount"), range_window(400, bucket=64), n=1
        ),
    },
    database=DB,
)


@pytest.mark.parametrize("mode", ["naive", "preagg"])
@pytest.mark.parametrize("num_shards", [None, 4])
def test_first_topn_primary_bucket_exact(mode, num_shards):
    """FIRST/LAST/TOPN over a plain (non-union) RANGE window compose from
    the persisted merge-order bucket families on the pre-agg path —
    exactly, matching the offline oracle row for row."""
    tx, _, k = _union_workload(seed=31)
    rep = verify_view(
        PRIMARY_VIEW, tx, num_keys=k, capacity=256, num_buckets=64,
        bucket_size=64, mode=mode, num_shards=num_shards,
    )
    assert rep.passed, rep.summary() + f" per-feature: {rep.per_feature}"
    for f, err in rep.per_feature.items():
        assert err == 0.0, f"{f}: max abs err {err} (expected exact)"


def _evo_view(with_families):
    feats = {"s": w_sum(Col("amount"), range_window(500, bucket=64))}
    if with_families:
        feats["first_r"] = w_first(
            Col("amount"), range_window(500, bucket=64)
        )
        feats["top1_r"] = w_topn_freq(
            Col("amount"), range_window(400, bucket=64), n=0
        )
    return FeatureView("evo", DB.primary, feats, database=DB)


@pytest.mark.parametrize("num_shards", [None, 4])
def test_merge_order_states_through_evolution(num_shards):
    """Adding FIRST/TOPN to a live lanes-only plane plans the merge-order
    bucket families mid-flight: the hot deploy rebuilds them from the
    ring-retained history, and a subsequent capacity re-lay carries them —
    both ending bit-identical to a cold rebuild + replay."""
    from repro.core import ScenarioPlane

    tx, _, k = _union_workload(seed=17)
    o = np.lexsort((tx["ts"], tx["acct"]))
    stream = {c: np.asarray(v)[o] for c, v in tx.items()}
    kw = dict(
        num_keys=k, num_shards=num_shards, capacity=256, num_buckets=64,
        bucket_size=64,
    )

    plane = ScenarioPlane([_evo_view(False)], **kw)
    assert plane.store.state.bagg.seq is None  # lanes-only: no families
    plane.ingest(stream)

    rep1 = plane.evolve([_evo_view(True)])  # families appear mid-flight
    assert rep1.exact, rep1.summary()
    bagg = plane.store.state.bagg
    assert bagg.seq is not None and bagg.xts is not None
    assert bagg.tts is not None

    rep2 = plane.evolve([_evo_view(True)], capacity=384)  # carry path
    assert rep2.exact, rep2.summary()

    cold = ScenarioPlane([_evo_view(True)], **{**kw, "capacity": 384})
    cold.ingest(stream)

    q = {c: v[-16:] for c, v in stream.items()}
    for mode in ("preagg", "naive"):
        got = plane.query("evo", dict(q), mode=mode)
        want = cold.query("evo", dict(q), mode=mode)
        for f in ("first_r", "top1_r"):
            np.testing.assert_array_equal(
                np.asarray(got[f]), np.asarray(want[f]),
                err_msg=f"{mode} {f}",
            )
        np.testing.assert_allclose(
            np.asarray(got["s"]), np.asarray(want["s"]), rtol=1e-6
        )

    # the family state itself matches the cold rebuild wherever observable
    # (fields of absent entries are don't-cares)
    # (compared in the logical per-key layout: (.., K, NB, *small))
    lead = int(num_shards is not None)
    hb = bucket_to_host(plane.store.state.bagg, lead)
    cb = bucket_to_host(cold.store.state.bagg, lead)
    np.testing.assert_array_equal(hb["seq"], cb["seq"])
    has = cb["xhas"]
    np.testing.assert_array_equal(hb["xhas"], has)
    for d in (0, 1):
        m = has[..., d]
        for nm in ("xts", "xpos"):
            np.testing.assert_array_equal(
                hb[nm][..., d][m], cb[nm][..., d][m], err_msg=nm,
            )
        np.testing.assert_array_equal(
            hb["xval"][..., d][m], cb["xval"][..., d][m]
        )
    valid = cb["tvalid"]
    np.testing.assert_array_equal(hb["tvalid"], valid)
    for nm in ("tts", "tpos"):
        np.testing.assert_array_equal(
            hb[nm][valid], cb[nm][valid], err_msg=nm,
        )
    hv = np.moveaxis(hb["tval"], -2, -1)  # (.., T, F) for masking
    cv = np.moveaxis(cb["tval"], -2, -1)
    np.testing.assert_array_equal(hv[valid], cv[valid], err_msg="tval")


def test_first_union_brute_force():
    """Offline FIRST over a union window vs a direct numpy oracle."""
    tx, wires, k = _union_workload(seed=5)
    from repro.core import OfflineEngine

    out = np.asarray(
        OfflineEngine().compute(
            UNION_VIEW,
            {c: np.asarray(v) for c, v in tx.items()},
            secondary={"wires": wires},
        )["first_u"]
    )
    W = 500
    for i in rng_idx(len(tx["ts"])):
        t_i, a_i = int(tx["ts"][i]), int(tx["acct"][i])
        rows = [
            (int(t), float(v))
            for t, v, a in zip(tx["ts"], tx["amount"], tx["acct"])
            if a == a_i and t_i - W < int(t) <= t_i and int(t) <= t_i
        ] + [
            (int(t), float(v))
            for t, v, a in zip(wires["ts"], wires["amount"], wires["acct"])
            if a == a_i and t_i - W < int(t) <= t_i
        ]
        want = min(rows)[1]  # oldest ts wins (unique ts by construction)
        assert out[i] == np.float32(want), i


def rng_idx(n, count=40, seed=3):
    return np.random.default_rng(seed).choice(n, size=min(count, n),
                                              replace=False)
