"""Row reads of per-key state equal the scalar-cell gathers they replaced.

The query program reads each queried key's ring and bucket state as whole
``(K, slot)`` rows at one pinned small-axis position and picks the slots
it needs on-chip (``x[f, s, keys]`` + ``storage.rotate_rows``).  The
reference below is the formulation those reads replaced, kept here and
not in the program: one ``cell_index`` gather per (row, slot, small-axis
position).  Each case compares the raw reads exactly, then the answers of
a store whose query program runs on the reference reads, bit for bit.
"""

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Col,
    Database,
    FeatureView,
    OnlineFeatureStore,
    ShardedOnlineStore,
    TableSchema,
    last_join,
    range_window,
    rows_window,
    w_count,
    w_distinct_approx,
    w_first,
    w_last,
    w_max,
    w_mean,
    w_min,
    w_std,
    w_sum,
    w_topn_freq,
)
from repro.core import storage as st
from repro.core.aggregates import LANES, agg_spec
from repro.core.storage import cell_index

# ---------------------------------------------------------------------------
# the reference: scalar-cell gathers
# ---------------------------------------------------------------------------


def ref_ring_gather(store, keys):
    cap = store.capacity
    cur = store.cursor[keys]
    offs = jnp.arange(cap, dtype=jnp.int32)[None, :]
    slots = (cur[:, None] - cap + offs) % cap
    valid = cur[:, None] - cap + offs >= 0
    kk = jnp.broadcast_to(keys[:, None], slots.shape)
    ts = store.ts[kk, slots]
    vals = store.vals[cell_index(kk, slots, (store.width,))]  # (Q, C, F)
    return ts, jnp.moveaxis(vals, -1, 0), valid


def ref_preagg_parts(self, wa, state, key, ts_q, ts_buf, valid, lane):
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

    M = self._max_mid(wa)
    mids = b_lo[:, None] + 1 + jnp.arange(M, dtype=jnp.int32)[None, :]
    mvalid = mids < b_q[:, None]
    slots = mids % nb
    kk = jnp.broadcast_to(key[:, None], slots.shape)
    bagg = state.bagg

    def cells(x, lane_axis=None):
        pin = None if lane_axis is None else {lane_axis: lane}
        return x[cell_index(kk, slots, x.shape[:-2], pin)]

    ok = mvalid & (bagg.bucket[kk, slots] == mids)
    spec = agg_spec(wa.agg)
    ms, mb, ext = {}, None, None
    if spec.state == "lanes":
        stats = cells(bagg.stats, 0)  # (Q, M, NUM_STATS)
        ms = {l: stats[..., LANES.index(l)] for l in spec.lanes}
    elif spec.state == "bitmap":
        mb = cells(bagg.bitmap, 0)
    elif spec.state == "extreme":
        d = 1 if spec.newest else 0
        ext = {
            "ts": cells(bagg.xts)[..., d],
            "pos": cells(bagg.xpos)[..., d],
            "val": cells(bagg.xval, 0)[..., d],
            "has": cells(bagg.xhas)[..., d],
        }
    elif spec.state == "tail":
        ext = {
            "ts": cells(bagg.tts),
            "pos": cells(bagg.tpos),
            "val": cells(bagg.tval, 0),
            "valid": cells(bagg.tvalid),
        }
    return raw, ms, mb, ok, ext


# ---------------------------------------------------------------------------
# views and streams
# ---------------------------------------------------------------------------

TX = TableSchema("tx", key="card", ts="ts", numeric=("amount",))

DB = Database(
    name="rr",
    primary=TableSchema(
        "tx", key="acct", ts="ts", numeric=("amount", "merchant")
    ),
    secondary=(
        TableSchema("wires", key="acct", ts="ts", numeric=("amount",)),
        TableSchema("accounts", key="acct", ts="ts", numeric=("limit",)),
        TableSchema("merchants", key="merchant", ts="ts", numeric=("risk",)),
    ),
)


def lanes_view(t_long, t_short, rows):
    amt = Col("amount")
    wl = range_window(t_long, bucket=10)
    ws = range_window(t_short, bucket=10)
    return FeatureView("rr_lanes", TX, {
        "sum_l": w_sum(amt, wl),
        "max_l": w_max(amt, wl),
        "min_l": w_min(amt, wl),
        "std_s": w_std(amt, ws),
        "mean_s": w_mean(amt, ws),
        "cnt_s": w_count(amt, ws),
        "big_s": w_count(amt > 50.0, ws),
        "dist_l": w_distinct_approx(amt, wl),
        "cnt_rows": w_count(amt, rows_window(rows)),
    })


def merge_order_view():
    amt = Col("amount")
    return FeatureView("rr_order", TX, {
        "first": w_first(amt, range_window(55, bucket=10)),
        "last": w_last(amt, range_window(55, bucket=10)),
        "top1": w_topn_freq(amt, range_window(45, bucket=10), n=0),
        "top2": w_topn_freq(amt, range_window(45, bucket=10), n=1),
        "sum": w_sum(amt, range_window(55, bucket=10)),
    })


def multi_table_view():
    amt = Col("amount")
    w = range_window(55, bucket=10)
    credit = last_join(Col("limit"), "accounts", on="acct", default=500.0)
    return FeatureView("rr_mt", features={
        "limit": credit,
        "mrisk": last_join(
            Col("risk"), "merchants", on="merchant", default=0.5
        ),
        "out_sum": w_sum(amt, w, union=("wires",)),
        "out_std": w_std(amt, w, union=("wires",)),
        "out_cnt": w_count(amt, w, union=("wires",)),
        "cnt_rows": w_count(amt, rows_window(6)),
        "plain_max": w_max(amt, w),
        "util": w_sum(amt, w, union=("wires",)) / credit,
    }, database=DB)


def rows_for(counts, t_lo, t_hi, rng, key_col="card"):
    """``counts[k]`` rows for key k at distinct ts in [t_lo, t_hi), as
    ts-ordered batches of 30 ts units each, every batch (key, ts)-sorted.
    Integer amounts make ties and TOPN frequencies common."""
    keys, ts = [], []
    for k, n in enumerate(counts):
        if n:
            keys.append(np.full(n, k, np.int32))
            ts.append(np.sort(rng.choice(
                np.arange(t_lo, t_hi), size=n, replace=False)))
    key = np.concatenate(keys).astype(np.int32)
    t = np.concatenate(ts).astype(np.int32)
    amt = rng.integers(0, 8, key.size).astype(np.float32) * 20.0
    out = []
    for lo in range(t_lo, t_hi, 30):
        sel = np.nonzero((t >= lo) & (t < lo + 30))[0]
        if sel.size:
            sel = sel[np.lexsort((t[sel], key[sel]))]
            out.append({key_col: key[sel], "ts": t[sel], "amount": amt[sel]})
    return out


def request(keys, ts, key_col="card", **extra):
    keys = np.asarray(keys, np.int32)
    cols = {
        key_col: keys,
        "ts": np.broadcast_to(np.asarray(ts, np.int32), keys.shape).copy(),
        "amount": np.arange(keys.size, dtype=np.float32) * 20.0,
    }
    cols.update(extra)
    return cols


# ---------------------------------------------------------------------------
# cases
# ---------------------------------------------------------------------------


def case_never_written(rng):
    # keys 3..7 never see a row: ring cursor 0, every bucket id -1
    return dict(
        view=lanes_view(95, 55, 6),
        kw=dict(num_keys=8, capacity=8, num_buckets=16),
        batches=rows_for([4, 9, 2, 0, 0, 0, 0, 0], 0, 150, rng),
        q=request([0, 3, 1, 7, 2, 5, 6, 4], 150),
    )


def case_ring_cursors(rng):
    # cursors 3 (< C), 8 (= C), 13 (> C), 16 (a multiple of C), 0
    return dict(
        view=lanes_view(95, 55, 6),
        kw=dict(num_keys=8, capacity=8, num_buckets=16),
        batches=rows_for([3, 8, 13, 16, 0, 24, 1, 7], 0, 200, rng),
        q=request(np.arange(8), [200, 190, 180, 170, 160, 150, 140, 130]),
    )


def case_early_windows(rng):
    # request ts below the window span: b_lo < 0 (and below -1)
    return dict(
        view=lanes_view(95, 55, 6),
        kw=dict(num_keys=4, capacity=16, num_buckets=16),
        batches=rows_for([10, 6, 12, 3], 0, 60, rng),
        q=request([0, 1, 2, 3, 0, 1, 2, 3], [0, 5, 11, 23, 37, 48, 54, 60]),
    )


def case_stale_bucket_slots(rng):
    # 60 buckets of history through an 8-slot bucket ring: every slot is
    # reused and holds older ids than the window asks for
    return dict(
        view=lanes_view(55, 45, 6),
        kw=dict(num_keys=4, capacity=32, num_buckets=8),
        batches=rows_for([60, 30, 5, 45], 0, 600, rng),
        q=request([0, 1, 2, 3, 0, 1, 2, 3],
                  [600, 605, 640, 700, 599, 611, 623, 655]),
    )


def case_max_middles(rng):
    # a 55-unit window over 10-unit buckets reads M = 6 middle slots and
    # covers up to 5 full buckets (at ts % 10 in 0..4); the bucket ring
    # has the fewest slots the layout allows (55 // 10 + 2 = 7)
    return dict(
        view=lanes_view(55, 45, 6),
        kw=dict(num_keys=2, capacity=64, num_buckets=7),
        batches=rows_for([50, 40], 0, 300, rng),
        q=request(np.repeat([0, 1], 10), np.tile(np.arange(290, 300), 2)),
    )


def case_rows_after_wrap(rng):
    # the 50-row ROWS window over a 64-slot ring after it wrapped
    return dict(
        view=lanes_view(95, 55, 50),
        kw=dict(num_keys=4, capacity=64, num_buckets=16),
        batches=rows_for([150, 64, 50, 49], 0, 400, rng),
        q=request([0, 1, 2, 3, 0, 1], [400, 400, 400, 400, 210, 390]),
    )


def case_merge_order(rng):
    # FIRST / LAST (extreme family) and TOPN_FREQ (tail family) buckets
    return dict(
        view=merge_order_view(),
        kw=dict(num_keys=4, capacity=16, num_buckets=16),
        batches=rows_for([40, 25, 3, 0], 0, 120, rng),
        q=request([0, 1, 2, 3, 0, 1], [120, 118, 95, 120, 60, 20]),
    )


def _multi_table_streams(rng):
    batches = rows_for([20, 12, 7, 0], 0, 100, rng, key_col="acct")
    for b in batches:
        b["merchant"] = (b["acct"] + b["ts"]) % 3
    sec = {
        "wires": rows_for([8, 0, 14, 3], 0, 100, rng, key_col="acct"),
        "accounts": [dict(
            acct=np.array([0, 1, 1, 2, 3], np.int32),
            ts=np.array([0, 10, 60, 5, 99], np.int32),
            limit=np.array([300, 400, 450, 900, 120], np.float32),
        )],
        "merchants": [dict(
            merchant=np.array([0, 1, 1, 2], np.int32),
            ts=np.array([20, 0, 70, 100], np.int32),
            risk=np.array([0.1, 0.7, 0.3, 0.9], np.float32),
        )],
    }
    q = request([0, 1, 2, 3, 0, 1, 2, 3], [100, 100, 70, 100, 40, 80, 100, 5],
                key_col="acct",
                merchant=np.array([0, 1, 2, 1, 1, 0, 2, 1], np.int32))
    return batches, sec, q


def case_multi_table(rng):
    # union rings (the request key's wires) and LAST JOINs (accounts by
    # acct, merchants by the request's merchant column)
    batches, sec, q = _multi_table_streams(rng)
    return dict(
        view=multi_table_view(),
        kw=dict(num_keys=4, capacity=16, num_buckets=16,
                secondary_num_keys={"merchants": 3}),
        batches=batches, sec=sec, q=q,
    )


def case_naive(rng):
    c = case_ring_cursors(rng)
    return dict(c, mode="naive")


def case_sharded(rng):
    batches, sec, q = _multi_table_streams(rng)
    return dict(
        view=multi_table_view(),
        kw=dict(num_keys=4, capacity=16, num_buckets=16,
                secondary_num_keys={"merchants": 3}, num_shards=4),
        batches=batches, sec=sec, q=q, sharded=True,
    )


CASES = {
    "never_written": case_never_written,
    "ring_cursors": case_ring_cursors,
    "early_windows": case_early_windows,
    "stale_bucket_slots": case_stale_bucket_slots,
    "max_middles": case_max_middles,
    "rows_after_wrap": case_rows_after_wrap,
    "merge_order": case_merge_order,
    "multi_table": case_multi_table,
    "naive": case_naive,
    "sharded": case_sharded,
}


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------


def _build(c):
    cls = ShardedOnlineStore if c.get("sharded") else OnlineFeatureStore
    store = cls(c["view"], bucket_size=10, **c["kw"])
    for b in c["batches"]:
        store.ingest(b)
    for t, batches in c.get("sec", {}).items():
        for b in batches:
            store.ingest_table(t, b)
    return store


def _assert_trees_equal(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        assert x.shape == y.shape and x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _read_pairs(store, state, keys, ts_q):
    """(row read, reference read) of every ring and of every RANGE wagg's
    bucket middles, for one store state."""
    pairs = []
    probe = jnp.concatenate([keys, jnp.array([-1, 10**6], jnp.int32)])
    for ring in [state.ring] + list(state.sec):
        k = jnp.clip(keys, 0, ring.num_keys - 1)
        # a negative key wraps and a key past K clamps, as indexing does
        for kk in (k, probe):
            pairs.append((st.ring_gather(ring, kk), ref_ring_gather(ring, kk)))
    ts_buf, _, valid = st.ring_gather(state.ring, keys)
    bagg = state.bagg
    for wa in store.waggs.values():
        spec = agg_spec(wa.agg)
        if wa.window.mode != "range" or (
            (spec.state == "extreme" and bagg.xts is None)
            or (spec.state == "tail" and bagg.tts is None)
        ):
            continue
        args = (wa, state, keys, ts_q, ts_buf, valid,
                store._lane_of[wa.arg.key])
        pairs.append(
            (store._preagg_parts(*args), ref_preagg_parts(store, *args))
        )
    return pairs


def _assert_reads_equal(store, state, keys, ts_q):
    pairs = jax.jit(functools.partial(_read_pairs, store))(state, keys, ts_q)
    for got, want in pairs:
        _assert_trees_equal(got, want)


@pytest.mark.parametrize("case", sorted(CASES))
def test_row_reads_match_cell_gathers(case, monkeypatch):
    c = CASES[case](np.random.default_rng(zlib.crc32(case.encode())))
    mode = c.get("mode", "preagg")
    store = _build(c)
    key_col = store.schema.key
    keys = jnp.asarray(c["q"][key_col], jnp.int32)
    ts_q = jnp.asarray(c["q"]["ts"], jnp.int32)

    if c.get("sharded"):
        # per shard, every local key against its own shard's state
        for s in range(store.num_shards):
            local = jax.tree.map(lambda a: a[s], store.state)
            lk = jnp.arange(store.state.ring.ts.shape[1], dtype=jnp.int32)
            _assert_reads_equal(store, local, lk, jnp.full_like(lk, 100))
    else:
        _assert_reads_equal(store, store.state, keys, ts_q)

    got = store.query(c["q"], mode=mode)
    with monkeypatch.context() as mp:
        mp.setattr(st, "ring_gather", ref_ring_gather)
        mp.setattr(OnlineFeatureStore, "_preagg_parts", ref_preagg_parts)
        ref_store = _build(c)
        want = ref_store.query(c["q"], mode=mode)
    assert sorted(got) == sorted(want)
    for f in want:
        np.testing.assert_array_equal(
            np.asarray(got[f]), np.asarray(want[f]), err_msg=f
        )
