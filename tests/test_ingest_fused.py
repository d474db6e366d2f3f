"""Fused ingest kernel: bit-exactness vs the split XLA sequence.

The contract (ISSUE 10): the one-pass Pallas kernel (ring scatter +
bucket pre-agg merge) must match the two-dispatch ``ring_ingest`` +
``bucket_ingest`` oracle bit-for-bit — at the raw kernel layer across
sequential batches, and end-to-end through ``OnlineFeatureStore`` /
``ShardedOnlineStore`` at shard counts {1, 4, 8}.  Runs in interpret
mode on CPU (the same kernel lowers via Mosaic on TPU).
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (
    Col,
    FeatureView,
    OnlineFeatureStore,
    ShardedOnlineStore,
    TableSchema,
    range_window,
    rows_window,
    w_count,
    w_distinct_approx,
    w_std,
    w_sum,
)
from repro.core import preagg as pg
from repro.core import storage as st
from repro.core.aggregates import row_bitmap
from repro.kernels.ingest.ops import fused_ingest

K, C, F, NB, BS = 7, 16, 3, 8, 50

STATE_NAMES = ("ring_ts", "ring_vals", "cursor", "bstats", "bbitmap", "bbucket")


def _init_state():
    ring = st.ring_init(K, C, F)
    bagg = pg.bucket_init(K, NB, F, BS)
    return (ring.ts, ring.vals, ring.cursor,
            bagg.stats, bagg.bitmap, bagg.bucket)


def _batch(rng, n, t_lo, t_hi, pad_to=None):
    key = np.sort(rng.integers(0, K, n)).astype(np.int32)
    ts = rng.integers(t_lo, t_hi, n).astype(np.int32)
    order = np.lexsort((ts, key))
    key, ts = key[order], ts[order]
    vals = rng.normal(size=(n, F)).astype(np.float32)
    if pad_to and pad_to > n:
        p = pad_to - n
        key = np.concatenate([key, np.full(p, K, np.int32)])
        ts = np.concatenate([ts, np.broadcast_to(ts[-1], (p,))])
        vals = np.concatenate([vals, np.zeros((p, F), np.float32)])
    return jnp.asarray(key), jnp.asarray(ts), jnp.asarray(vals)


def test_fused_ingest_kernel_bit_exact_sequential_batches():
    """Raw kernel layer: five sequential padded batches, every state
    array equal bit-for-bit after each one (incl. sumsq — the lane where
    fma contraction would show as a 1-ulp drift)."""
    rng = np.random.default_rng(0)
    state_x, state_p = _init_state(), _init_state()
    plan = [(20, 0, 300, 32), (15, 250, 380, 16), (9, 350, 400, 16),
            (30, 380, 390, 32), (25, 390, 700, 32)]
    for step, (n, lo, hi, pad) in enumerate(plan):
        k, t, v = _batch(rng, n, lo, hi, pad)
        state_x = fused_ingest(*state_x, k, t, v, bucket_size=BS, impl="xla")
        state_p = fused_ingest(*state_p, k, t, v, bucket_size=BS,
                               impl="pallas", interpret=True)
        for nm, a, b in zip(STATE_NAMES, state_x, state_p):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"step {step} {nm}"
            )


def test_fused_ingest_all_pad_batch_is_noop():
    """A batch of only sentinel pads must leave every array untouched."""
    rng = np.random.default_rng(1)
    state = _init_state()
    k, t, v = _batch(rng, 12, 0, 200, pad_to=16)
    state = fused_ingest(*state, k, t, v, bucket_size=BS,
                         impl="pallas", interpret=True)
    pk = jnp.full((16,), K, jnp.int32)
    pt = jnp.full((16,), 500, jnp.int32)
    pv = jnp.zeros((16, F), jnp.float32)
    after = fused_ingest(*state, pk, pt, pv, bucket_size=BS,
                         impl="pallas", interpret=True)
    for nm, a, b in zip(STATE_NAMES, state, after):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=nm
        )


def test_kernel_row_bitmap_matches_library():
    """The kernel ORs the library's own ``aggregates.row_bitmap`` lift into
    its bucket bitmaps — pin it on edge values (signed zeros, ±3e38, the
    lane identities) with one row per key, so each bucket's bitmap is
    exactly its row's lift."""
    v = np.array(
        [0.0, -0.0, 1.0, -1.0, 3.0e38, -3.0e38, 0.5], np.float32
    )
    key = jnp.arange(K, dtype=jnp.int32)
    vals = jnp.asarray(np.repeat(v[:, None], F, axis=1))
    ts = jnp.zeros((K,), jnp.int32)
    out = fused_ingest(*_init_state(), key, ts, vals, bucket_size=BS,
                       impl="pallas", interpret=True)
    bitmap = np.asarray(out[STATE_NAMES.index("bbitmap")])   # (F, K, NB)
    want = np.asarray(row_bitmap(jnp.asarray(v)))
    for f in range(F):
        np.testing.assert_array_equal(bitmap[f, :, 0], want)


SCHEMA = TableSchema(name="tx", key="uid", ts="ts", numeric=("amount",),
                     categorical=("mcc",))


def _view():
    return FeatureView("t", SCHEMA, {
        "s": w_sum(Col("amount"), range_window(300, bucket=32)),
        "sd": w_std(Col("amount"), range_window(300, bucket=32)),
        "c": w_count(Col("amount"), rows_window(10)),
        "d": w_distinct_approx(Col("amount"), range_window(300, bucket=32)),
    })


def _stream(rng, n, lo, hi, k=6):
    key = rng.integers(0, k, n).astype(np.int32)
    ts = rng.integers(lo, hi, n).astype(np.int32)
    o = np.lexsort((ts, key))
    return dict(
        uid=key[o], ts=ts[o],
        amount=rng.gamma(2.0, 40.0, n).astype(np.float32),
        mcc=rng.integers(0, 30, n).astype(np.int32),
    )


STORE_KW = dict(num_keys=6, capacity=64, num_buckets=16, bucket_size=32)


@pytest.mark.parametrize("num_shards", [1, 4, 8])
def test_store_fused_vs_split_bit_exact(num_shards):
    """End-to-end: a store on the fused Pallas path equals the split XLA
    path bit-for-bit — state arrays and query answers — at every shard
    count, through routing, padding and epoch splitting."""
    rng = np.random.default_rng(40 + num_shards)
    if num_shards == 1:
        sx = OnlineFeatureStore(_view(), **STORE_KW)
        sp = OnlineFeatureStore(_view(), **STORE_KW)
    else:
        sx = ShardedOnlineStore(_view(), num_shards=num_shards, **STORE_KW)
        sp = ShardedOnlineStore(_view(), num_shards=num_shards, **STORE_KW)
    sp.ingest_impl = "pallas"
    sp.ingest_interpret = True
    sp._build_fns()
    for lo, hi, n in [(0, 300, 40), (250, 500, 25), (480, 900, 50)]:
        b = _stream(rng, n, lo, hi)
        sx.ingest(dict(b))
        sp.ingest(dict(b))
    np.testing.assert_array_equal(
        np.asarray(sx.state.ring.ts), np.asarray(sp.state.ring.ts))
    np.testing.assert_array_equal(
        np.asarray(sx.state.ring.vals), np.asarray(sp.state.ring.vals))
    np.testing.assert_array_equal(
        np.asarray(sx.state.ring.cursor), np.asarray(sp.state.ring.cursor))
    np.testing.assert_array_equal(
        np.asarray(sx.state.bagg.stats), np.asarray(sp.state.bagg.stats))
    np.testing.assert_array_equal(
        np.asarray(sx.state.bagg.bitmap), np.asarray(sp.state.bagg.bitmap))
    np.testing.assert_array_equal(
        np.asarray(sx.state.bagg.bucket), np.asarray(sp.state.bagg.bucket))
    q = _stream(rng, 8, 900, 950)
    for mode in ("naive", "preagg"):
        rx = sx.query(dict(q), mode=mode)
        rp = sp.query(dict(q), mode=mode)
        for f in rx:
            np.testing.assert_array_equal(
                np.asarray(rx[f]), np.asarray(rp[f]),
                err_msg=f"S={num_shards} {mode}:{f}",
            )
