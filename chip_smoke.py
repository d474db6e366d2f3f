"""Smoke test of the feature store on a TPU: the serving path at a real key count.

    python chip_smoke.py              # one chip: fraud view, 131,072 keys
    python chip_smoke.py --chips 4    # four chips: the sharded plane only

One process, normal entry points only (``FeatureService.build``, the
store's ``ingest`` / ``ingest_table``, ``FeatureService.request``,
``OfflineEngine``).  History is generated from ``--seed``, (key, ts)-sorted
batches spread over several hours of event time; request batches (unique
keys per batch, after the history) are answered online and checked
against the offline engine over the same history: COUNT/MAX-derived
features bit for bit, SUM/MEAN/STD within the consistency module's
scale-aware tolerance.  The kernel dispatch counters must show the Pallas
ingest (and, on four chips, the Pallas route kernel) ran.

Exits non-zero, printing no result line, when JAX finds no TPU or any
check fails.  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402  (jax-free)

# one chip: the fraud view at the issue's widths
ONE_KEYS, CAPACITY, NUM_BUCKETS = 131_072, 256, 512
HOURS = 8                       # event-time span of the history
BATCHES, BATCH_ROWS = 32, 4_096  # ingest batches x rows per batch
HOT_KEYS = 2_048                # half the rows land on these (deep windows)
REQ_BATCHES, REQ_ROWS = 4, 512
# four chips: the sharded view, key count scaled to ~4x the one-chip state
FOUR_KEYS, MERCHANTS = 581_632, 4_096

# the consistency module's scale-aware tolerance (core/consistency.py)
RTOL, ATOL_SCALE = 2e-4, 1e-3


def _fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def _history(rng, num_keys, extra=None):
    """(key, ts)-sorted batches: half the rows on HOT_KEYS keys spread over
    the key space, half uniform; batch b covers its own slice of event
    time; (key, ts) pairs are unique (no window ties)."""
    import numpy as np

    span = HOURS * 3600 // BATCHES
    stride = num_keys // HOT_KEYS
    out = []
    for b in range(BATCHES):
        n = BATCH_ROWS
        hot = rng.random(n) < 0.5
        key = np.where(
            hot, rng.integers(0, HOT_KEYS, n) * stride,
            rng.integers(0, num_keys, n),
        ).astype(np.int64)
        ts = rng.integers(b * span, (b + 1) * span, n).astype(np.int64)
        pair = np.unique(key << 32 | ts)  # sorted by (key, ts)
        m = len(pair)
        cols = {
            "key": (pair >> 32).astype(np.int32),
            "ts": (pair & 0xFFFFFFFF).astype(np.int32),
            "amount": rng.gamma(1.5, 60.0, m).astype(np.float32),
        }
        for c, hi in (extra or {}).items():
            cols[c] = rng.integers(0, hi, m).astype(np.int32)
        out.append(cols)
    return out


def _requests(rng, num_keys, t0, extra=None):
    """Request batches after the history, unique keys per batch (half hot)."""
    import numpy as np

    stride = num_keys // HOT_KEYS
    out = []
    for b in range(REQ_BATCHES):
        hot = rng.choice(HOT_KEYS, REQ_ROWS // 2, replace=False) * stride
        cold = rng.choice(num_keys, REQ_ROWS, replace=False)
        key = np.unique(np.concatenate([hot, cold]))[:REQ_ROWS]
        n = len(key)
        cols = {
            "key": key.astype(np.int32),
            "ts": (t0 + 60 * b + rng.integers(0, 60, n)).astype(np.int32),
            "amount": rng.gamma(1.5, 60.0, n).astype(np.float32),
        }
        for c, hi in (extra or {}).items():
            cols[c] = rng.integers(0, hi, n).astype(np.int32)
        out.append(cols)
    return out


def _rename(cols, key_col):
    return {(key_col if c == "key" else c): v for c, v in cols.items()}


def _concat(batches):
    import numpy as np

    return {c: np.concatenate([b[c] for b in batches]) for c in batches[0]}


def _check_vs_offline(view, online, offline, exact, tag):
    """Online answers vs the offline engine: ``exact`` features bit for
    bit, the rest within the scale-aware tolerance.  Returns max errors."""
    import numpy as np

    worst = {}
    for f in view.features:
        a = np.asarray(offline[f], np.float64)
        b = np.asarray(online[f], np.float64)
        if f in exact:
            if not np.array_equal(a, b):
                bad = int(np.sum(a != b))
                _fail(f"{tag}: {f} differs from offline at {bad} rows")
        else:
            scale = float(np.percentile(np.abs(a), 99)) if a.size else 1.0
            if not np.allclose(a, b, rtol=RTOL, atol=ATOL_SCALE * max(1.0, scale)):
                _fail(f"{tag}: {f} outside tolerance of offline")
        worst[f] = float(np.max(np.abs(a - b), initial=0.0))
    return worst


def _dispatches():
    from repro.obs import get_telemetry

    snap = get_telemetry().metrics.snapshot()
    out = {}
    for name in ("kernel_dispatch_total", "kernel_cutover_total"):
        for s in snap.get(name, {}).get("series", []):
            lab = s["labels"]
            out[f"{name}{{{','.join(f'{k}={v}' for k, v in lab.items())}}}"] = s["value"]
    return out


def _require_dispatch(kernel, impl="pallas"):
    d = _dispatches()
    got = {k: v for k, v in d.items() if f"kernel={kernel}," in k}
    if not got.get(f"kernel_dispatch_total{{kernel={kernel},impl={impl}}}"):
        _fail(f"{kernel} never dispatched impl={impl}: {got}")
    others = [k for k in got if "kernel_dispatch_total" in k and f"impl={impl}" not in k]
    if others or any("cutover" in k for k in d if kernel in k):
        _fail(f"{kernel} also took another path: {got}")


def _state_bytes(state):
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))


def _ingest_all(svc, batches, key_col, table=None):
    """Ingest (key, ts)-sorted batches; returns (first-batch s, rest s)."""
    import jax

    store = svc.store
    times = []
    for b in batches:
        t = time.perf_counter()
        cols = _rename(b, key_col)
        if table is None:
            store.ingest(cols)
        else:
            store.ingest_table(table, cols)
        jax.block_until_ready(store.state)
        times.append(time.perf_counter() - t)
    return times[0], sum(times[1:])


def one_chip(seed: int) -> None:
    import jax
    import numpy as np

    from repro.core import OfflineEngine
    from repro.scenarios import fraud_view
    from repro.serve.service import FeatureService

    rng = np.random.default_rng(seed)
    view = fraud_view()
    key_col = view.schema.key
    t = time.perf_counter()
    svc = FeatureService.build(
        "fraud", view, num_keys=ONE_KEYS, capacity=CAPACITY,
        num_buckets=NUM_BUCKETS,
    )
    jax.block_until_ready(svc.store.state)
    sb = _state_bytes(svc.store.state)
    print(f"build: {ONE_KEYS} keys, C={CAPACITY}, NB={NUM_BUCKETS}, "
          f"state {sb} bytes ({sb / 2**30:.3f} GiB) in "
          f"{time.perf_counter() - t:.2f} s")

    hist = _history(rng, ONE_KEYS)
    first, rest = _ingest_all(svc, hist, key_col)
    rows = sum(len(b["key"]) for b in hist)
    print(f"ingest: {rows} rows in {len(hist)} batches; first batch "
          f"(compile) {first:.2f} s, remaining {rest:.3f} s host clock")
    _require_dispatch("fused_ingest")

    history = _rename(_concat(hist), key_col)
    exact = {"tx_count_1h", "tx_count_50", "amt_max_6h", "big_ratio_1h"}
    engine = OfflineEngine()
    worst = {}
    t_end = HOURS * 3600
    for i, req in enumerate(_requests(rng, ONE_KEYS, t_end + 1)):
        req = _rename(req, key_col)
        t = time.perf_counter()
        online = svc.request(req, ingest=False)
        dt = time.perf_counter() - t
        allc = {c: np.concatenate([history[c], req[c]]) for c in req}
        off = engine.compute(view, allc)
        n = len(req[key_col])
        off = {f: np.asarray(v)[-n:] for f, v in off.items()}
        w = _check_vs_offline(view, online, off, exact, f"request batch {i}")
        for f, e in w.items():
            worst[f] = max(worst.get(f, 0.0), e)
        print(f"request batch {i}: {n} rows in {dt:.3f} s host clock; "
              "matches offline")
    print("max |online - offline| per feature: "
          + json.dumps({f: worst[f] for f in sorted(worst)}))
    print("dispatches: " + json.dumps(_dispatches()))


def four_chips(seed: int) -> None:
    import jax
    import numpy as np

    from repro.core import OfflineEngine
    from repro.scenarios import sharded_view
    from repro.serve.service import FeatureService

    devs = jax.devices()
    if len(devs) < 4:
        _fail(f"--chips 4 needs 4 devices, JAX sees {len(devs)}")
    rng = np.random.default_rng(seed)
    view = sharded_view()
    K = FOUR_KEYS
    t = time.perf_counter()
    svc = FeatureService.build(
        "fraud_sharded", view, num_keys=K, sharded=True, num_shards=4,
        capacity=CAPACITY, num_buckets=NUM_BUCKETS,
        secondary_num_keys={"merchants": MERCHANTS},
    )
    store = svc.store
    jax.block_until_ready(store.state)
    if store.mesh.devices.size != 4:
        _fail(f"shard mesh holds {store.mesh.devices.size} devices, not 4")
    for leaf in jax.tree.leaves(store.state):
        shards = leaf.addressable_shards
        on = {s.device for s in shards}
        if len(shards) != 4 or len(on) != 4 or any(
            s.data.shape[0] != 1 for s in shards
        ):
            _fail(f"state leaf {leaf.shape} not one shard per device: "
                  f"{[(s.device, s.data.shape) for s in shards]}")
    sb = _state_bytes(store.state)
    print(f"build: {K} keys over 4 shards on {len(on)} devices, state "
          f"{sb} bytes ({sb / 2**30:.3f} GiB, "
          f"{sb / 4 / 2**30:.3f} GiB per device) in "
          f"{time.perf_counter() - t:.2f} s")

    db = view.database
    acc_upd = K // 8
    accounts = {
        "account": np.concatenate(
            [np.arange(K), rng.integers(0, K, acc_upd)]).astype(np.int32),
        "ts": np.concatenate(
            [np.zeros(K), rng.integers(1, HOURS * 3600, acc_upd)]
        ).astype(np.int32),
        "credit_limit": rng.uniform(500.0, 20_000.0, K + acc_upd).astype(
            np.float32),
        "risk_score": rng.beta(2.0, 8.0, K + acc_upd).astype(np.float32),
    }
    merchants = {
        "merchant": np.concatenate(
            [np.arange(MERCHANTS), rng.integers(0, MERCHANTS, MERCHANTS)]
        ).astype(np.int32),
        "ts": np.concatenate(
            [np.zeros(MERCHANTS), rng.integers(1, HOURS * 3600, MERCHANTS)]
        ).astype(np.int32),
        "avg_ticket": rng.gamma(2.0, 40.0, 2 * MERCHANTS).astype(np.float32),
        "fraud_reports": rng.poisson(2.0, 2 * MERCHANTS).astype(np.float32),
    }
    secondary = {"accounts": accounts, "merchants": merchants}
    for name, cols in secondary.items():
        sch = db.table(name)
        order = np.lexsort((cols[sch.ts], cols[sch.key]))
        secondary[name] = {c: v[order] for c, v in cols.items()}
        store.ingest_table(name, secondary[name])
    wires = _history(rng, K)[: BATCHES // 4]
    first_w, rest_w = _ingest_all(svc, wires, "account", table="wires")
    secondary["wires"] = _rename(_concat(wires), "account")
    hist = _history(rng, K, extra={"merchant": MERCHANTS})
    first, rest = _ingest_all(svc, hist, "account")
    rows = sum(len(b["key"]) for b in hist)
    print(f"ingest: accounts {len(accounts['account'])}, merchants "
          f"{len(merchants['merchant'])}, wires "
          f"{len(secondary['wires']['account'])} rows, transactions {rows} "
          f"rows in {len(hist)} batches; first batch (compile) {first:.2f} s,"
          f" remaining {rest:.3f} s host clock")
    _require_dispatch("fused_ingest")

    history = _rename(_concat(hist), "account")
    exact = {"credit_limit", "merchant_ticket", "outflow_cnt_1h"}
    engine = OfflineEngine()
    worst = {}
    reqs = _requests(rng, K, HOURS * 3600 + 1, extra={"merchant": MERCHANTS})
    for i, req in enumerate(reqs):
        req = _rename(req, "account")
        store.device_routing = True
        t = time.perf_counter()
        dev = svc.request(req, ingest=False)
        dt = time.perf_counter() - t
        store.device_routing = False
        host = svc.request(req, ingest=False)
        store.device_routing = True
        for f in view.features:
            if not np.array_equal(np.asarray(dev[f]), np.asarray(host[f])):
                _fail(f"request batch {i}: {f} device-routed != host-routed")
        allc = {c: np.concatenate([history[c], req[c]]) for c in req}
        off = engine.compute(view, allc, secondary)
        n = len(req["account"])
        off = {f: np.asarray(v)[-n:] for f, v in off.items()}
        w = _check_vs_offline(view, dev, off, exact, f"request batch {i}")
        for f, e in w.items():
            worst[f] = max(worst.get(f, 0.0), e)
        print(f"request batch {i}: {n} rows, fused device route {dt:.3f} s "
              "host clock; equals host-routed oracle bit for bit; matches "
              "offline")
    _require_dispatch("route_rank")
    print("max |online - offline| per feature: "
          + json.dumps({f: worst[f] for f in sorted(worst)}))
    print("dispatches: " + json.dumps(_dispatches()))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        _fail(f"no TPU: JAX found {devs[0].platform} devices")
    print(f"device: {devs[0].platform} {devs[0].device_kind} x {len(devs)}")
    print(f"compile cache: {enable_compile_cache()}")
    t = time.perf_counter()
    (one_chip if args.chips == 1 else four_chips)(args.seed)
    print(f"total {time.perf_counter() - t:.1f} s")
    stats = devs[0].memory_stats() or {}
    print(f"device 0 peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }}))


if __name__ == "__main__":
    main()
