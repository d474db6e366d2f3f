"""Dispatcher for the fused ingest kernel.

``fused_ingest(...)`` applies one (key, ts)-sorted ingest batch to the
six primary-store state arrays — ring scatter (cursor advance + lane
writes) AND bucket pre-agg merge — choosing between the Pallas one-pass
kernel and the split XLA oracle (``impl="xla"``, exactly the old
``ring_ingest`` + ``bucket_ingest`` sequence).  Both paths are
bit-identical; callers (``OnlineFeatureStore._ingest_pure``, vmapped
per-shard in ``core/shard.py``) treat the choice as a pure perf knob.

The row→block plumbing the kernel needs (run boundaries, ring slots,
valid masks) is O(N) int32 scan/gather work computed here and handed to
the kernel as scalar-prefetch operands — the payload arrays are only
ever touched inside the kernel's single pass.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import note_dispatch
from repro.kernels.ingest.ingest import KEY_GROUP, fused_ingest_pallas
from repro.kernels.ingest.ref import fused_ingest_ref

__all__ = ["fused_ingest", "fused_ingest_apply", "resolve_ingest_impl"]


def resolve_ingest_impl(impl: str = "auto") -> str:
    """Resolve ``impl="auto"`` against the active backend (host-side)."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return impl


def _ffill2(flags: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray):
    """Carry (a, b) at flagged rows forward over unflagged rows."""

    def comb(x, y):
        fx, ax, bx = x
        fy, ay, by = y
        return fx | fy, jnp.where(fy, ay, ax), jnp.where(fy, by, bx)

    return jax.lax.associative_scan(comb, (flags, a, b))


def _seg_cumsum(vals: jnp.ndarray, starts: jnp.ndarray) -> jnp.ndarray:
    """Inclusive segmented cumsum (segments begin where ``starts``)."""

    def comb(x, y):
        fx, vx = x
        fy, vy = y
        return fx | fy, jnp.where(fy, vy, vx + vy)

    _, out = jax.lax.associative_scan(comb, (starts, vals))
    return out


def _ingest_plan(
    key: jnp.ndarray,
    ts: jnp.ndarray,
    cursor: jnp.ndarray,
    *,
    num_keys: int,
    capacity: int,
    num_buckets: int,
    bucket_size: int,
) -> Tuple[jnp.ndarray, ...]:
    """The 8 (N,) int32 scalar-prefetch arrays driving the kernel's pass.

    Sentinel pad rows (key == num_keys) inherit the nearest real row's
    (key, bucket) — forward fill, then backward fill for leading pads —
    so the kernel's block index never jumps to a pad-only block and every
    key group's blocks are visited in one consecutive run.  Pad rows write
    nothing (``valid`` gates every state mutation).
    """
    n = key.shape[0]
    key = jnp.asarray(key, jnp.int32)
    valid = key < jnp.int32(num_keys)
    bid_raw = jnp.asarray(ts, jnp.int32) // jnp.int32(bucket_size)
    kz = jnp.where(valid, key, 0)
    bz = jnp.where(valid, bid_raw, 0)
    hf, kf, bf = _ffill2(valid, kz, bz)
    hb, kb, bb = (
        jnp.flip(x, 0)
        for x in _ffill2(
            jnp.flip(valid, 0), jnp.flip(kz, 0), jnp.flip(bz, 0)
        )
    )
    ckey = jnp.where(hf, kf, jnp.where(hb, kb, 0))
    cbid = jnp.where(hf, bf, jnp.where(hb, bb, 0))

    first = jnp.ones((1,), bool)
    kchange = jnp.concatenate([first, ckey[1:] != ckey[:-1]])
    grp = ckey // jnp.int32(KEY_GROUP)
    gchange = jnp.concatenate([first, grp[1:] != grp[:-1]])
    schange = kchange | jnp.concatenate([first, cbid[1:] != cbid[:-1]])
    send = jnp.concatenate([schange[1:], first])
    seg_id = jnp.cumsum(schange.astype(jnp.int32)) - 1
    seg_has_valid = (
        jnp.zeros((n,), jnp.int32).at[seg_id].max(valid.astype(jnp.int32))
    )
    flush = send & (seg_has_valid[seg_id] == 1)

    # ring slot: cursor0[key] + (valid rank within the key run), mod C —
    # identical to ring_ingest's (cursor[key] + rank) % cap for real rows
    cnt = _seg_cumsum(valid.astype(jnp.int32), kchange)
    slot_r = (cursor[ckey] + cnt - 1) % jnp.int32(capacity)
    slot_b = cbid % jnp.int32(num_buckets)

    as_i32 = lambda x: x.astype(jnp.int32)  # noqa: E731
    return (
        ckey, as_i32(gchange), as_i32(schange), as_i32(flush),
        as_i32(valid), slot_r, cbid, slot_b,
    )


def fused_ingest(
    ring_ts: jnp.ndarray,    # (K, C) int32
    ring_vals: jnp.ndarray,  # (F, K, C) f32 (stored layout)
    cursor: jnp.ndarray,     # (K,) int32
    bstats: jnp.ndarray,     # (F, NUM_STATS, K, NB) f32
    bbitmap: jnp.ndarray,    # (F, K, NB) int32
    bbucket: jnp.ndarray,    # (K, NB) int32
    key: jnp.ndarray,        # (N,) int32 sorted by (key, ts); pad key == K
    ts: jnp.ndarray,         # (N,) int32
    vals: jnp.ndarray,       # (N, F) f32
    *,
    bucket_size: int,
    impl: str = "auto",
    interpret: bool = False,
) -> Tuple[jnp.ndarray, ...]:
    """Returns the six updated state arrays (ring ts/vals/cursor, bucket
    stats/bitmap/ids)."""
    impl = resolve_ingest_impl(impl)
    note_dispatch("fused_ingest", impl)
    return _fused_ingest(
        ring_ts, ring_vals, cursor, bstats, bbitmap, bbucket,
        key, ts, vals,
        bucket_size=bucket_size, impl=impl, interpret=interpret,
    )


def fused_ingest_apply(
    ring_ts, ring_vals, cursor, bstats, bbitmap, bbucket, key, ts, vals,
    *, bucket_size: int, impl: str, interpret: bool = False,
) -> Tuple[jnp.ndarray, ...]:
    """Trace-level body of :func:`fused_ingest` — embeddable inside a
    caller's own jit (the online store's ingest fn, vmapped per shard on
    the sharded plane).  ``impl`` must be pre-resolved
    (:func:`resolve_ingest_impl`); the caller owns dispatch counting."""
    if impl == "xla":
        return fused_ingest_ref(
            ring_ts, ring_vals, cursor, bstats, bbitmap, bbucket,
            key, ts, vals, bucket_size=bucket_size,
        )
    plan = _ingest_plan(
        key, ts, cursor,
        num_keys=ring_ts.shape[0], capacity=ring_ts.shape[1],
        num_buckets=bbucket.shape[1], bucket_size=bucket_size,
    )
    rts, rvals, bst, bbm, bid = fused_ingest_pallas(
        ring_ts, ring_vals, bstats, bbitmap, bbucket,
        ts, vals, plan, interpret=interpret,
    )
    # the cursor advance is a (K,) scatter-add, as in ring_ingest (pad
    # rows carry key == K and drop)
    cur = cursor.at[key].add(jnp.ones_like(key), mode="drop")
    return rts, rvals, cur, bst, bbm, bid


_fused_ingest = functools.partial(
    jax.jit, static_argnames=("bucket_size", "impl", "interpret")
)(fused_ingest_apply)
