"""CPU rehearsal of every cell at a tiny key count (four-chip cells on
four host devices).

Each run drives the whole harness except its look for a chip: the seeded
generator, the open-loop window through the router and the connector's
micro-batches, the reference comparison.  A sound run is ``correct``; the
control (the reference with bfloat16 values in the program's place) and a
program broken under the timed path are not.  It prints no device metric.
"""

import copy
import json
import os
import sys
import time

import numpy as np
import pytest

FB = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, FB)
sys.path.insert(0, os.path.join(os.path.dirname(FB), "src"))

from repro.hostdevices import force_host_devices  # noqa: E402

# the four-chip cell's sharded store wants as many host devices
force_host_devices(8)

import harness  # noqa: E402

KEYS = 512
# the cells rehearsed here: every cell of BENCHMARK.json, and the cells of
# ``rehearsed/<name>.json`` that it does not hold (PERF.md, Open
# questions: fraud_cards.write50 spread too widely on one chip and once
# halted the device; fraud_accounts.write50 waits for a deployment with a
# public source), so a cell out of the benchmark keeps its rehearsal
REHEARSED = os.path.join(FB, "tests", "rehearsed")
BENCH_CELLS = harness.benchmark()["workloads"]


def _rehearsed(name):
    with open(os.path.join(REHEARSED, name)) as f:
        return json.load(f)


PENDING = [c for c in map(_rehearsed, sorted(os.listdir(REHEARSED)))
           if c["name"] not in {w["name"] for w in BENCH_CELLS}]
CELLS = {w["name"]: w for w in BENCH_CELLS + PENDING}
ONE_CHIP = [n for n, w in CELLS.items() if w["chips"] == 1]


def tiny(cell):
    cfg = harness.traffic.load("configs", cell["config"])
    tables = copy.deepcopy(cfg["tables"])
    for t in tables.values():
        t["num_keys"] = min(t["num_keys"], KEYS)
        for col, spec in t["columns"].items():
            if spec[0] == "uniform_int":  # join keys into a smaller table
                t["columns"][col] = ["uniform_int", min(spec[1], KEYS)]
    store = dict(cfg["store"], num_keys=KEYS, capacity=32)
    if "secondary_num_keys" in store:
        store["secondary_num_keys"] = {
            t: tables[t]["num_keys"] for t in store["secondary_num_keys"]}
    return {
        "store": store,
        "tables": tables,
        "scheduler": {"max_batch": 16, "max_wait_us": 5000, "buckets": [16]},
        "ingest_max_rows": 8,
        "history": dict(cfg["history"], rows=4000, batch_rows=1024),
    }


def rehearse(name, seed=11, control=False):
    import jax

    cell = CELLS[name]
    return harness.run(
        cell, seed, 1.0, False, devices=jax.devices()[: cell["chips"]],
        t_proc=time.perf_counter(), cfg_override=tiny(cell),
        mix_override={"rate_ops_per_s": 300}, control=control)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_rehearsal_is_correct_and_control_is_not(name):
    r = rehearse(name, control=True)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] == 300
    assert r["device"]["platform"] == "cpu"
    assert r["device"]["count"] == CELLS[name]["chips"]
    assert set(r["metrics"]) == {"request_p50_ms", "request_p95_ms",
                                 "freshness_p95_ms", "ops_per_s", "setup_s"}
    assert "busy_s" not in r["device"]
    ctl = r["readings"]["control"]
    assert (ctl["exact_mismatch"] > r["checks"]["exact_mismatch"]["limit"]
            or ctl["value_err"] > r["checks"]["value_err"]["limit"])


def _state_unchanged(orig):
    def ingest(self, key, ts, lanes):
        return None
    return ingest


def _half_batch(orig):
    def ingest(self, key, ts, lanes):
        h = key.shape[0] // 2
        if h:
            orig(self, key[:h], ts[:h], lanes[:h])
    return ingest


def _answer_altered(feature):
    def wrap(orig):
        def query(self, columns, *a, **k):
            out = dict(orig(self, columns, *a, **k))
            v = np.array(out[feature], np.float32)
            v[0] += 1.0
            out[feature] = v
            return out
        return query
    return wrap


def _exchange_left_out(orig):
    # every routed row stays on shard 0 instead of moving to its key's
    # shard; the query still routes to the owner
    def route_ids(self, key, upper=None):
        shard, local = orig(self, key, upper)
        return np.zeros_like(shard), local
    return route_ids


FAULTS = [
    ("fraud_cards.read95", "OnlineFeatureStore", "_ingest_padded",
     _state_unchanged),
    ("fraud_cards.read95", "OnlineFeatureStore", "_ingest_padded",
     _half_batch),
    ("fraud_cards.read95", "OnlineFeatureStore", "query",
     _answer_altered("tx_count_1h")),
    ("fraud_accounts.write50", "ShardedOnlineStore", "_ingest_padded",
     _state_unchanged),
    ("fraud_accounts.write50", "ShardedOnlineStore", "_ingest_padded",
     _half_batch),
    ("fraud_accounts.write50", "ShardedOnlineStore", "query",
     _answer_altered("credit_limit")),
    ("fraud_accounts.write50", "ShardedOnlineStore", "_route_ids",
     _exchange_left_out),
]


@pytest.mark.parametrize(
    "cell,cls,attr,wrap", FAULTS,
    ids=[f"{c}-{w.__name__ if w.__name__ != 'wrap' else 'answer'}"
         for c, _, _, w in FAULTS])
def test_broken_timed_path_is_not_correct(cell, cls, attr, wrap,
                                          monkeypatch):
    from repro.core.online import OnlineFeatureStore
    from repro.core.shard import ShardedOnlineStore

    klass = {"OnlineFeatureStore": OnlineFeatureStore,
             "ShardedOnlineStore": ShardedOnlineStore}[cls]
    monkeypatch.setattr(klass, attr, wrap(getattr(klass, attr)))
    r = rehearse(cell, seed=12)
    assert not r["correct"], r["checks"]


def test_same_seed_same_inputs_and_every_seed_the_same_counts():
    import traffic

    cell = CELLS[ONE_CHIP[0]]
    cfg = harness.traffic.load("configs", cell["config"])
    cfg.update(tiny(cell))
    mix = traffic.load("traffic", cell["traffic"])

    def draw(seed):
        rng = np.random.default_rng(seed)
        return (traffic.history(rng, cfg, mix["keys"]),
                traffic.schedule(rng, cfg, mix, 2.0))

    (h1, s1), (h2, s2), (_, s3) = draw(2**31 + 5), draw(2**31 + 5), draw(7)
    assert all(np.array_equal(a[1]["card"], b[1]["card"])
               for a, b in zip(h1, h2))
    assert np.array_equal(s1.reads["card"], s2.reads["card"])
    assert np.array_equal(s1.reads["due"], s2.reads["due"])
    assert s1.attempted == s3.attempted
    assert len(s1.reads["due"]) == len(s3.reads["due"])
    assert not np.array_equal(s1.reads["card"], s3.reads["card"])


def test_every_rehearsed_cell_is_rehearsed_in_or_out_of_the_benchmark():
    names = {c["name"] for c in map(_rehearsed, os.listdir(REHEARSED))}
    assert names <= set(CELLS)
    assert {c["name"] for c in PENDING} == names - {
        w["name"] for w in BENCH_CELLS}
    for c in CELLS.values():
        assert harness.traffic.load("traffic", c["traffic"])["name"] == \
            c["traffic"]
        assert harness.traffic.load("configs", c["config"])["name"] == \
            c["config"]

