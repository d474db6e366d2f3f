"""The one traffic generator: every mix is a data file it reads.

A mix (``featbench/traffic/<name>.json``) fixes the offered rate, the share
of reads, how the writes split over tables, and how keys are drawn.  A
configuration (``featbench/configs/<name>.json``) fixes the tables'
columns and the history loaded at set-up.  Everything is drawn from the
seed with NumPy; the program under test receives only the arrays.

Arrivals are an open loop: ``round(rate * seconds)`` operations whose due
times are sorted uniform draws over the window (a Poisson process
conditioned on its count), so every seed offers the same number of reads
and writes per table, in another order.

Keys follow YCSB's core workloads: a zipfian over a large item space
(``item_count``, constant ``theta``), scrambled onto the key space by
FNV-1a 64 (``ScrambledZipfianGenerator``), so the hot keys are the same
for every seed and spread over the key space.  ``uniform`` draws keys
uniformly.
"""

from __future__ import annotations

import json
import os
from typing import Dict

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(1099511628211)


def load(kind: str, name: str) -> dict:
    """``featbench/<kind>/<name>.json`` (kind: configs or traffic)."""
    path = os.path.join(HERE, kind, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def fnv64(x: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64``: FNV over the 8 little-endian bytes of a
    long, made non-negative."""
    v = np.asarray(x, np.int64).view(np.uint64).copy()
    h = np.full(v.shape, _FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= v & np.uint64(0xFF)
            h *= _FNV_PRIME
            v >>= np.uint64(8)
    return np.abs(h.view(np.int64))


def zipfian_rank(rng, n: int, item_count: int, theta: float,
                 zetan: float) -> np.ndarray:
    """Ranks in [0, item_count) by YCSB's ``ZipfianGenerator.nextLong``
    (Gray et al.), with ``zetan`` = zeta(item_count, theta) given."""
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / item_count) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(n)
    uz = u * zetan
    rank = (item_count * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    rank = np.where(uz < 1.0 + 0.5 ** theta, 1, rank)
    return np.where(uz < 1.0, 0, rank)


def draw_keys(rng, n: int, num_keys: int, spec: dict) -> np.ndarray:
    dist = spec["dist"]
    if dist == "uniform":
        return rng.integers(0, num_keys, n).astype(np.int32)
    if dist == "scrambled_zipfian":
        rank = zipfian_rank(
            rng, n, int(spec["item_count"]), float(spec["theta"]),
            float(spec["zetan"]),
        )
        return (fnv64(rank) % num_keys).astype(np.int32)
    raise ValueError(f"unknown key distribution {dist!r}")


def draw_column(rng, n: int, spec: list) -> np.ndarray:
    """One value column: ``["gamma", shape, scale]``, ``["beta", a, b]``,
    ``["uniform", lo, hi]``, ``["uniform_int", hi]``, ``["poisson", lam]``."""
    kind, *args = spec
    if kind == "gamma":
        return rng.gamma(args[0], args[1], n).astype(np.float32)
    if kind == "beta":
        return rng.beta(args[0], args[1], n).astype(np.float32)
    if kind == "uniform":
        return rng.uniform(args[0], args[1], n).astype(np.float32)
    if kind == "uniform_int":
        return rng.integers(0, int(args[0]), n).astype(np.int32)
    if kind == "poisson":
        return rng.poisson(args[0], n).astype(np.float32)
    raise ValueError(f"unknown column distribution {kind!r}")


def rows(rng, table: dict, keys: np.ndarray, ts: np.ndarray) -> Dict[str, np.ndarray]:
    """Columns of ``len(keys)`` rows of a table described in a config."""
    n = len(keys)
    out = {table["key"]: np.asarray(keys, np.int32),
           "ts": np.asarray(ts, np.int32)}
    for col, spec in table["columns"].items():
        out[col] = draw_column(rng, n, spec)
    return out


def sort_rows(cols: Dict[str, np.ndarray], key: str) -> Dict[str, np.ndarray]:
    """Rows ordered by (key, ts), as the ingest contract requires (stable,
    so rows of one (key, ts) keep their arrival order)."""
    order = np.lexsort((cols["ts"], cols[key]))
    return {c: v[order] for c, v in cols.items()}


def history(rng, cfg: dict, keyspec: dict):
    """Set-up history of the streamed tables: ``history.rows`` rows spread
    evenly over ``history.span_s`` event seconds, in ingest batches of
    ``batch_rows`` split over the tables by ``history.tables``, then for
    each table a ramp of batches of every size from 1 to
    ``ingest_max_rows`` (so set-up runs every micro-batch shape the
    window can deliver).  Each batch is (key, ts)-sorted and later in
    event time than the one before.

    Returns a list of (table, columns) in ingest order."""
    h = cfg["history"]
    total, span = int(h["rows"]), int(h["span_s"])
    shares = h["tables"]
    names = sorted(shares)
    ramp = list(range(1, int(cfg["ingest_max_rows"]) + 1))
    body = total - len(names) * sum(ramp)
    step = int(h["batch_rows"])
    batches = [{t: int(round(step * shares[t])) for t in names}
               for _ in range(body // step)]
    batches += [{t: n} for n in ramp for t in names]
    out = []
    done = 0
    for b in batches:
        t_lo = span * done // total
        done += sum(b.values())
        t_hi = max(span * done // total, t_lo + 1)
        for name, m in b.items():
            if m == 0:
                continue
            tab = cfg["tables"][name]
            keys = draw_keys(rng, m, int(tab["num_keys"]), keyspec)
            ts = rng.integers(t_lo, t_hi, m)
            out.append((name, sort_rows(rows(rng, tab, keys, ts), tab["key"])))
    return out


def static_tables(rng, cfg: dict) -> Dict[str, Dict[str, np.ndarray]]:
    """Slowly-changing tables loaded once at set-up (LAST JOIN targets):
    a ts=0 row for every key, then ``revisions`` x keys updates at
    uniform times over the history."""
    out = {}
    span = int(cfg["history"]["span_s"])
    for name, tab in cfg["tables"].items():
        if "static" not in tab:
            continue
        k = int(tab["num_keys"])
        extra = int(round(k * float(tab["static"]["revisions"])))
        keys = np.concatenate([np.arange(k), rng.integers(0, k, extra)])
        ts = np.concatenate([np.zeros(k, np.int64),
                             rng.integers(1, span, extra)])
        out[name] = sort_rows(rows(rng, tab, keys, ts), tab["key"])
    return out


class Schedule:
    """The window's operations, due times in seconds from the window start.

    ``reads``: columns of the request rows plus ``due``.  ``writes``: per
    table, columns plus ``due``, in due order."""

    def __init__(self, reads: Dict[str, np.ndarray],
                 writes: Dict[str, Dict[str, np.ndarray]]):
        self.reads = reads
        self.writes = writes

    @property
    def attempted(self) -> int:
        return len(self.reads["due"]) + sum(
            len(w["due"]) for w in self.writes.values())


def schedule(rng, cfg: dict, mix: dict, seconds: float) -> Schedule:
    n = int(round(float(mix["rate_ops_per_s"]) * seconds))
    due = np.sort(rng.random(n)) * seconds
    kinds = ["read"] * int(round(n * float(mix["read_share"])))
    rest = left = n - len(kinds)
    shares = mix["write_tables"]
    names = sorted(shares)
    for j, name in enumerate(names):
        m = left if j == len(names) - 1 else int(round(rest * shares[name]))
        kinds += [name] * m
        left -= m
    kinds = np.asarray(kinds)[rng.permutation(n)]
    t0 = int(cfg["event_t0"])
    keyspec = mix["keys"]
    prim = cfg["tables"][cfg["primary"]]

    def at(sel):
        d = due[sel]
        return d, (t0 + np.floor(d)).astype(np.int32)

    d, ts = at(kinds == "read")
    reads = rows(rng, prim, draw_keys(rng, len(d), int(prim["num_keys"]),
                                      keyspec), ts)
    reads["due"] = d
    writes = {}
    for name in names:
        d, ts = at(kinds == name)
        tab = cfg["tables"][name]
        w = rows(rng, tab, draw_keys(rng, len(d), int(tab["num_keys"]),
                                     keyspec), ts)
        w["due"] = d
        writes[name] = w
    return Schedule(reads, writes)
