"""Plain reference of the feature semantics the configurations state.

NumPy only; imports nothing of the program and takes nothing it made.  It
is handed the rows the harness generated, in the order the harness
ingested them, and answers each request as of the rows ingested before
the batch that served it.  Every sum is taken in float64.

The semantics are the deployment's, as each configuration file states
them under ``guarantees``:

* A key keeps its newest ``capacity`` rows (OpenMLDB's ``latest`` TTL) in
  its ring, and one pre-aggregate per ``bucket_s`` event-second bucket
  over every row it was sent.
* A RANGE window of ``span`` seconds at request time ``ts`` covers the
  rows with ``ts - span < t <= ts``: its full buckets from the
  pre-aggregates, its two edge buckets from the rows the ring still holds.
* A ROWS window of ``n`` is the newest ``n - 1`` ring rows with
  ``t <= ts``.  A WINDOW UNION adds a secondary table's ring rows in the
  same time range.  Every window also holds the request row itself.
* A LAST JOIN takes the newest row with ``t <= ts`` of the joined key
  (the latest ingested on equal ``t``) that the ring holds, else the
  default.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


class Table:
    """One table's ingested rows, grouped by key in ingest order."""

    def __init__(self, key, ts, cols: Dict[str, np.ndarray], capacity: int):
        key = np.asarray(key, np.int64)
        ts = np.asarray(ts, np.int64)
        order = np.argsort(key, kind="stable")
        self.capacity = int(capacity)
        self.key = key[order]
        self.ts = ts[order]
        self.seq = order.astype(np.int64)  # ingest position of each row
        same = self.key[1:] == self.key[:-1]
        if np.any(same & (self.ts[1:] < self.ts[:-1])):
            raise ValueError("a key's rows were ingested out of ts order")
        self.cols = {c: np.asarray(v)[order] for c, v in cols.items()}
        self._kseq = self.key << 32 | self.seq
        self._kts = self.key << 32 | self.ts
        self._sums: Dict[str, tuple] = {}
        self._maxes: Dict[str, list] = {}

    # -- positions in the key-grouped order ---------------------------------

    def block(self, qkey, cutoff):
        """(start, end) of each key's rows ingested before ``cutoff``, and
        the start of what its ring holds."""
        k = np.asarray(qkey, np.int64) << 32
        s = np.searchsorted(self._kseq, k, "left")
        p = np.searchsorted(self._kseq, k | np.asarray(cutoff, np.int64),
                            "left")
        return s, p, np.maximum(s, p - self.capacity)

    def at_ts(self, qkey, t, side, lo, hi):
        t = np.clip(np.asarray(t, np.int64), 0, (1 << 31) - 1)
        pos = np.searchsorted(self._kts, np.asarray(qkey, np.int64) << 32 | t,
                              side)
        return np.clip(pos, lo, hi)

    # -- range reductions ---------------------------------------------------

    def _prefix(self, col):
        if col not in self._sums:
            v = self.cols[col].astype(np.float64)
            z = np.zeros(1)
            self._sums[col] = (np.concatenate([z, np.cumsum(v)]),
                               np.concatenate([z, np.cumsum(v * v)]))
        return self._sums[col]

    def _range_max(self, col, a, b):
        """max over rows [a, b) (-inf where empty), by a sparse table."""
        if col not in self._maxes:
            lv = [self.cols[col].astype(np.float64)]
            while (1 << len(lv)) <= len(lv[0]):
                h = 1 << (len(lv) - 1)
                prev = lv[-1]
                lv.append(np.maximum(prev[:-h], prev[h:]))
            self._maxes[col] = lv
        lv = self._maxes[col]
        n = b - a
        out = np.full(a.shape, -np.inf)
        ok = n > 0
        j = np.zeros(a.shape, np.int64)
        j[ok] = np.floor(np.log2(n[ok])).astype(np.int64)
        for lvl in np.unique(j[ok]):
            m = ok & (j == lvl)
            x = lv[lvl]
            out[m] = np.maximum(x[a[m]], x[b[m] - (1 << lvl)])
        return out

    def stats(self, col, ranges):
        """sum, count, sum of squares and max of ``col`` over a union of
        disjoint row ranges [(a, b), ...]."""
        cs, cs2 = self._prefix(col)
        out = {"sum": 0.0, "count": 0, "sumsq": 0.0, "max": -np.inf}
        for a, b in ranges:
            b = np.maximum(a, b)
            out["sum"] = out["sum"] + cs[b] - cs[a]
            out["sumsq"] = out["sumsq"] + cs2[b] - cs2[a]
            out["count"] = out["count"] + (b - a)
            out["max"] = np.maximum(out["max"], self._range_max(col, a, b))
        return out

    # -- windows ---------------------------------------------------------------

    def range_window(self, col, req, cutoff, span, bucket_s):
        """Rows of a RANGE window on the pre-aggregated path (see module
        docstring), as stats (the request row not yet included)."""
        key, ts = req["key"], np.asarray(req["ts"], np.int64)
        s, p, r0 = self.block(key, cutoff)
        lo = ts - span + 1
        b_q = ts // bucket_s
        b_lo = (ts - span) // bucket_s
        mid_a = self.at_ts(key, (b_lo + 1) * bucket_s, "left", s, p)
        mid_b = self.at_ts(key, b_q * bucket_s, "left", s, p)
        tail_a = self.at_ts(key, np.maximum(lo, b_q * bucket_s), "left", r0, p)
        tail_b = self.at_ts(key, ts, "right", r0, p)
        head_a = self.at_ts(key, lo, "left", r0, p)
        head_b = np.where(
            b_lo != b_q,
            self.at_ts(key, (b_lo + 1) * bucket_s, "left", r0, p), head_a)
        return self.stats(col, [(head_a, head_b), (mid_a, mid_b),
                                (tail_a, tail_b)])

    def ring_range(self, col, req, cutoff, span):
        """Ring rows with ``ts - span < t <= ts`` (a WINDOW UNION's part
        from this table)."""
        key, ts = req["key"], np.asarray(req["ts"], np.int64)
        s, p, r0 = self.block(key, cutoff)
        a = self.at_ts(key, ts - span + 1, "left", r0, p)
        b = self.at_ts(key, ts, "right", r0, p)
        return self.stats(col, [(a, b)])

    def rows_window(self, col, req, cutoff, n):
        """The newest ``n - 1`` ring rows with ``t <= ts``."""
        key, ts = req["key"], np.asarray(req["ts"], np.int64)
        s, p, r0 = self.block(key, cutoff)
        b = self.at_ts(key, ts, "right", r0, p)
        return self.stats(col, [(np.maximum(r0, b - (n - 1)), b)])

    def last_join(self, col, jkey, ts, cutoff, default):
        s, p, r0 = self.block(jkey, cutoff)
        b = self.at_ts(jkey, ts, "right", r0, p)
        found = b > r0
        v = self.cols[col].astype(np.float64)
        return np.where(found, v[np.maximum(b - 1, 0)], default)


def combine(*parts):
    """Merge window stats (sum/count/sumsq/max) of disjoint row sets."""
    out = dict(parts[0])
    for q in parts[1:]:
        out = {"sum": out["sum"] + q["sum"], "count": out["count"] + q["count"],
               "sumsq": out["sumsq"] + q["sumsq"],
               "max": np.maximum(out["max"], q["max"])}
    return out


def with_row(st, v):
    """Add the request row's own value to window stats."""
    v = np.asarray(v, np.float64)
    return combine(st, {"sum": v, "count": 1, "sumsq": v * v, "max": v})


def mean(st):
    return st["sum"] / np.maximum(st["count"], 1)


def std(st):
    c = np.maximum(st["count"], 1)
    m = st["sum"] / c
    return np.sqrt(np.maximum(st["sumsq"] / c - m * m, 0.0))


def to_bf16(x):
    """Round float32 values to the nearest bfloat16 (ties to even), kept
    as float32: the values as a bfloat16 store would hold them."""
    b = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32)
