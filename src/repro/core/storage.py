"""Compact time-series storage — the TPU adaptation of FeatInsight's store.

The paper keeps rows in a skiplist sorted by (key, timestamp) with a compact
row encoding (fixed-width fields inline, variable-width out-of-line) and
lock-free CAS updates.  None of that ports to a TPU; what *does* port is the
invariant the skiplist buys: **per-key, timestamp-ordered, O(1)-appendable
recent history**.  We realize it as a structure-of-arrays ring buffer:

  ts    : (K, C)     int32   per-key ring of row timestamps
  vals  : (F, K, C)  float32 per-key ring of encoded row payloads, one
                             (K, C) plane per lane (see "Stored layout")
  cursor: (K,)       int32   next write slot (monotone; slot = cursor % C)

* "Compact row encoding"  -> the codec below: fixed-width numeric fields are
  stored as f32 lanes; variable-width/categorical fields are hashed to
  signatures *at ingest* (64-bit mix folded to `bits`), so every row is a
  fixed-width vector.  This is the paper's own signature trick promoted into
  the storage codec.
* "Lock-free CAS updates" -> pure functional batched scatter with buffer
  donation: one fused XLA scatter applies a whole ingest batch in-place
  (donated), giving contention-free semantics by construction.
* "TTL / batch deletion"  -> rows age out by ring overwrite; reads mask by
  (ts > now - ttl), so expiry is O(0) — the paper's "timestamp ordering and
  batch deletion" with the deletion cost removed entirely.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hashing import fold_hash

__all__ = [
    "TableSchema", "Database", "RowCodec", "RingStore",
    "ring_init", "ring_ingest", "ring_gather", "cell_index", "rotate_rows",
    "to_logical", "to_stored",
]


# ---------------------------------------------------------------------------
# Stored layout of per-key state
# ---------------------------------------------------------------------------
#
# Every per-key state array (ring rows here, bucket pre-aggregates in
# preagg.py) is stored *key-minor*: its small axes (lanes F, the NUM_STATS
# stat lanes, merge-order slots) lead, and its two long axes -- the key K,
# then the per-key slot axis (ring slot C or bucket slot NB) -- are the two
# minor ones.  A TPU tiles the two minor axes of an array as (8 sublanes x
# 128 lanes), so (K, slot) tiles densely where a minor F=2 or NUM_STATS=5
# axis would pad 64x or 25x.
#
# Device reads take whole (K, slot) rows at one pinned small-axis position
# (``x[f, s, keys]``: one contiguous SLOT-wide slice per queried key) and
# pick the slots they need on-chip with vector ops (``rotate_rows``).  A
# gather whose slice spans a small axis (``x[:, :, keys]``,
# ``jnp.take(x[f], keys, axis=1)``) makes XLA relayout the whole state
# first: a state-sized copy per call.
# Ingest scatters address single cells with scalar indices (``cell_index``).
# Host code (migration, backfill) works in the logical per-key layout
# (K, slot, *small) and converts at its edges with ``to_logical`` /
# ``to_stored``; ``lead`` counts leading batch axes (the shard axis).


def to_logical(x: np.ndarray, lead: int = 0) -> np.ndarray:
    """Stored (*batch, *small, K, SLOT) -> logical (*batch, K, SLOT, *small)."""
    if x.ndim - lead <= 2:
        return x
    return np.moveaxis(x, (-2, -1), (lead, lead + 1))


def to_stored(x: np.ndarray, lead: int = 0) -> np.ndarray:
    """Logical (*batch, K, SLOT, *small) -> stored (*batch, *small, K, SLOT)."""
    if x.ndim - lead <= 2:
        return x
    return np.moveaxis(x, (lead, lead + 1), (-2, -1))


def cell_index(k, s, small: Tuple[int, ...], pin: Optional[Dict[int, int]] = None):
    """Scalar-element index into a stored ``(*small, K, SLOT)`` array.

    For every position of ``k``/``s`` (same shape B) and of each swept small
    axis, addresses cell ``(..., k, s)``; ``pin`` fixes small axes to one
    value instead of sweeping them.  ``x[cell_index(...)]`` (or
    ``x.at[...]``) yields shape ``B + swept`` -- the logical per-key order
    -- through one scalar gather/scatter.
    """
    pin = pin or {}
    k = jnp.asarray(k, jnp.int32)
    s = jnp.asarray(s, jnp.int32)
    b = k.shape
    swept = tuple(n for ax, n in enumerate(small) if ax not in pin)
    shape = b + swept
    out = []
    d = len(b)
    for ax in range(len(small)):
        if ax in pin:
            out.append(jnp.full(shape, pin[ax], jnp.int32))
        else:
            out.append(jax.lax.broadcasted_iota(jnp.int32, shape, d))
            d += 1
    tail = (1,) * len(swept)
    out.append(jnp.broadcast_to(k.reshape(b + tail), shape))
    out.append(jnp.broadcast_to(s.reshape(b + tail), shape))
    return tuple(out)


def rotate_rows(x: jnp.ndarray, shift: jnp.ndarray) -> jnp.ndarray:
    """``out[..., q, j] = x[..., q, (j + shift[q]) % N]`` for ``x`` of shape
    ``(..., Q, N)`` and ``shift`` (Q,) in [0, N).

    A barrel shifter: one static roll per bit of the shift, each taken
    where that bit is set -- vector selects only, no gather.
    """
    n = x.shape[-1]
    for b in range(max(n - 1, 0).bit_length()):
        step = 1 << b
        rolled = jnp.concatenate([x[..., step:], x[..., :step]], axis=-1)
        x = jnp.where(((shift >> b) & 1)[..., None] == 1, rolled, x)
    return x


@dataclasses.dataclass(frozen=True)
class TableSchema:
    """Schema of a raw source table.

    numeric: fixed-width f32 fields stored verbatim.
    categorical: variable-width fields, hashed to `cat_bits`-bit signatures
    at ingest (they arrive as arbitrary int ids; strings are pre-tokenized
    at the import boundary — TPU tensors cannot hold strings).
    """

    name: str
    key: str
    ts: str
    numeric: Tuple[str, ...] = ()
    categorical: Tuple[str, ...] = ()
    cat_bits: int = 20

    @property
    def columns(self) -> Tuple[str, ...]:
        return self.numeric + self.categorical

    @property
    def width(self) -> int:
        return len(self.numeric) + len(self.categorical)


@dataclasses.dataclass(frozen=True)
class Database:
    """A primary table plus named secondary tables — the multi-table plane.

    Mirrors FeatInsight's database grouping (the 2018 PHM dataset's 17
    tables live in one database): the *primary* table drives feature
    computation row-by-row; *secondary* tables feed point-in-time LAST
    JOINs (their ``key`` column is matched against a primary join column)
    and WINDOW UNION streams (their ``key`` column shares the primary
    key's id space).
    """

    name: str
    primary: TableSchema
    secondary: Tuple[TableSchema, ...] = ()

    def __post_init__(self) -> None:
        names = [self.primary.name] + [t.name for t in self.secondary]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate table names in database: {names}")

    @property
    def tables(self) -> Tuple[TableSchema, ...]:
        return (self.primary,) + self.secondary

    def table(self, name: str) -> TableSchema:
        for t in self.tables:
            if t.name == name:
                return t
        raise KeyError(
            f"table {name!r} not in database {self.name!r} "
            f"(has {[t.name for t in self.tables]})"
        )

    def is_secondary(self, name: str) -> bool:
        return any(t.name == name for t in self.secondary)


class RowCodec:
    """Encode heterogeneous rows into fixed-width f32 vectors (and back)."""

    def __init__(self, schema: TableSchema):
        self.schema = schema
        self._col_index = {c: i for i, c in enumerate(schema.columns)}

    def encode(self, columns: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        """dict of (N,) columns -> (N, F) f32 payload."""
        lanes: List[jnp.ndarray] = []
        for c in self.schema.numeric:
            lanes.append(jnp.asarray(columns[c], jnp.float32))
        for c in self.schema.categorical:
            # zlib.crc32, not hash(): Python string hashing is randomized
            # per-process and would break cross-run determinism.
            salt = zlib.crc32(c.encode()) & 0x7FFF
            sig = fold_hash(
                [jnp.asarray(columns[c])], salt=salt,
                bits=self.schema.cat_bits,
            )
            lanes.append(sig.astype(jnp.float32))
        return jnp.stack(lanes, axis=-1)

    def column(self, payload: jnp.ndarray, name: str) -> jnp.ndarray:
        return payload[..., self._col_index[name]]

    def col_id(self, name: str) -> int:
        return self._col_index[name]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RingStore:
    """Per-key timestamp-ordered ring buffers (functional)."""

    ts: jnp.ndarray       # (K, C) int32
    vals: jnp.ndarray     # (F, K, C) f32, stored layout
    cursor: jnp.ndarray   # (K,) int32, monotone row count per key

    def tree_flatten(self):
        return (self.ts, self.vals, self.cursor), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def num_keys(self) -> int:
        return self.ts.shape[-2]

    @property
    def capacity(self) -> int:
        return self.ts.shape[-1]

    @property
    def width(self) -> int:
        return self.vals.shape[-3]


def ring_init(num_keys: int, capacity: int, width: int) -> RingStore:
    return RingStore(
        ts=jnp.full((num_keys, capacity), -2147483648, jnp.int32),
        vals=jnp.zeros((width, num_keys, capacity), jnp.float32),
        cursor=jnp.zeros((num_keys,), jnp.int32),
    )


def ring_ingest(
    store: RingStore,
    key: jnp.ndarray,   # (N,) int32 in [0, K)
    ts: jnp.ndarray,    # (N,) int32, batch sorted by (key, ts)
    vals: jnp.ndarray,  # (N, F) f32 payloads
) -> RingStore:
    """Apply a whole ingest batch as one fused scatter (donated in callers).

    Rows must be pre-sorted by (key, ts) — the import pipeline guarantees it
    (mirroring the paper: data is pre-sorted by key and timestamp).  Multiple
    rows per key per batch are supported: each row's slot is
    cursor[key] + (its rank within its key segment in this batch).
    """
    n = key.shape[0]
    cap = store.capacity
    idx = jnp.arange(n, dtype=jnp.int32)
    is_start = jnp.concatenate([jnp.array([True]), key[1:] != key[:-1]])
    seg_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, idx, 0)
    )
    rank = idx - seg_start  # position of each row within its key's batch rows

    slot = (store.cursor[key] + rank) % cap
    ts_new = store.ts.at[key, slot].set(ts, mode="drop")
    vals_new = store.vals.at[cell_index(key, slot, (store.width,))].set(
        vals, mode="drop"
    )
    # per-key appended count = segment length; scatter-add ones
    cursor_new = store.cursor.at[key].add(jnp.ones((n,), jnp.int32))
    return RingStore(ts=ts_new, vals=vals_new, cursor=cursor_new)


def ring_gather(
    store: RingStore, keys: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Read each queried key's ring unrolled oldest->newest.

    One row read per plane (timestamps, then each lane), each row rotated
    to start at the key's oldest slot ``cursor % C``.  Returns
    (ts (Q, C), vals (F, Q, C), valid (Q, C)).
    """
    cap = store.capacity
    cur = store.cursor[keys]  # (Q,)
    # slot order oldest..newest: cursor - C .. cursor - 1  (mod C)
    offs = jnp.arange(cap, dtype=jnp.int32)[None, :]
    age_rank = cur[:, None] - cap + offs  # absolute row index; <0 => never written
    valid = age_rank >= 0
    shift = cur % cap
    ts = rotate_rows(store.ts[keys], shift)
    vals = rotate_rows(
        jnp.stack([store.vals[f, keys] for f in range(store.width)]), shift
    )
    return ts, vals, valid
