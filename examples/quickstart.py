"""Quickstart — the FeatInsight §3.1 end-to-end loop in ~80 lines.

  1. import data        (CSV -> typed columns)
  2. create features    (declarative DAG -> feature view + lineage)
  3. offline compute    (export a training set)
  4. online service     (ingest stream, point queries)
  5. consistency check  (offline batch == online incremental)

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import io

import numpy as np

from repro.core import (
    Col, FeatureRegistry, FeatureView, OfflineEngine, OnlineFeatureStore,
    range_window, w_count, w_mean, w_sum,
)
from repro.core.consistency import verify_view
from repro.core.storage import TableSchema
from repro.data import load_csv
from repro.hostdevices import device_line

print(device_line())

# -- 1. import data (the paper's "Data Import" button) -----------------------
SCHEMA = TableSchema(name="orders", key="user", ts="ts",
                     numeric=("price",), categorical=("product",))
CSV = io.StringIO(
    "user,ts,price,product\n" + "\n".join(
        f"{u},{t},{round(p, 2)},{pr}"
        for u, t, p, pr in zip(
            np.random.default_rng(0).integers(0, 4, 200),
            np.sort(np.random.default_rng(1).integers(0, 5000, 200)),
            np.random.default_rng(2).gamma(2.0, 30.0, 200),
            np.random.default_rng(3).integers(0, 10, 200),
        )
    )
)
table = load_csv(CSV, SCHEMA)
print(f"imported {len(table['user'])} rows into table {SCHEMA.name!r}")

# -- 2. create features (visual DAG -> SQL in the paper; a DSL here) ----------
price = Col("price")
w1k = range_window(1000, bucket=64)
view = FeatureView(
    name="user_spend", schema=SCHEMA,
    features={
        "spend_1k": w_sum(price, w1k),
        "orders_1k": w_count(price, w1k),
        "avg_1k": w_mean(price, w1k),
        "big_order": price > 100.0,
    },
    description="per-user trailing-1000s spend features",
)
registry = FeatureRegistry()
registry.register(view)
print("\nlineage of 'spend_1k':")
lin = view.lineage()["spend_1k"]
print(f"  view={lin['view']} v{lin['version']}  columns={lin['columns']}")
print(f"  sql: {lin['sql']}")

# -- 3. offline compute + training-set export ---------------------------------
engine = OfflineEngine()
feats = engine.compute(view, table)
print(f"\noffline features: {list(feats)} over {len(feats['spend_1k'])} rows")

# -- 4. online feature service ------------------------------------------------
store = OnlineFeatureStore(view, num_keys=4, num_buckets=64, bucket_size=64)
order = np.lexsort((table["ts"], table["user"]))
store.ingest({c: v[order] for c, v in table.items()})
req = {"user": np.arange(4, dtype=np.int32),
       "ts": np.full(4, 5001, np.int32),
       "price": np.full(4, 10.0, np.float32),
       "product": np.zeros(4, np.int32)}
online = store.query(req)
print("\nonline point-query (4 users):")
for f, v in online.items():
    print(f"  {f:10s} {np.asarray(v).round(2)}")

# -- 5. consistency verification ----------------------------------------------
report = verify_view(view, table, num_keys=4, num_buckets=64, bucket_size=64)
print(f"\nconsistency: {report.summary()}")
assert report.passed
print("\nquickstart OK")
