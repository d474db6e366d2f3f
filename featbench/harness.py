"""One run of one cell: set-up, the open-loop window, the check, the metrics.

The harness plays two parts around the system under test:

* the clients: reads are request rows submitted to the service's router
  (``ShardRouter.submit`` / ``pump``, ``ingest=False``) at their due time;
* the stream connector: at every poll tick it hands the store the writes
  that came due since the last tick, as (key, ts)-sorted micro-batches of
  at most ``ingest_max_rows`` rows (a consumer's ``max.poll.records``),
  through ``store.ingest`` / ``store.ingest_table``.

One thread drives both, as the store is driven in one process.  Every
latency starts at the operation's due time, so time a read or a write
spent waiting behind other work counts.  The harness logs the order of
every ingest and every pump, and after the window compares every answer
the window served with the plain reference (``featbench/reference.py``
and the configuration's ``configs/<name>_ref.py``).

Everything that belongs to one configuration, mix or per-layer metric is
found by name under ``featbench/``: ``configs/<name>.json`` (+ ``_ref.py``),
``traffic/<name>.json`` and ``metrics/<name>.py``.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

import check
import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DRAIN_LIMIT_S = 60.0


class Fail(RuntimeError):
    """The run cannot produce a result (no chip, unknown device, bad cell)."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise Fail(f"no workload {name!r} in BENCHMARK.json")


def load_peaks(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise Fail(f"device kind {kind!r} is not in featbench/peaks.json")
    return table["devices"][kind]


def enable_compile_cache() -> None:
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``,
    else a fixed ``.jax_cache`` in the checkout), holding every program."""
    import jax
    from repro.compile_cache import enable_compile_cache as program_cache

    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCounter:
    """Counts XLA backend compiles while ``on``."""

    def __init__(self):
        import jax

        self.on = False
        self.count = 0

        def listen(event, duration, **_):
            if self.on and event == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


# ---------------------------------------------------------------------------
# the deployment
# ---------------------------------------------------------------------------


class Deployment:
    """The service under test, built through its normal entry points, and
    the log of every row it was sent, in ingest order."""

    def __init__(self, cfg: dict, devices):
        from repro import scenarios
        from repro.serve.service import FeatureService

        self.cfg = cfg
        view = getattr(scenarios, cfg["view"])()
        kw = dict(cfg["store"])
        if kw.get("sharded"):
            kw["mesh"] = _mesh(devices, kw["num_shards"])
        self.svc = FeatureService.build(cfg["name"], view, **kw)
        self.store = self.svc.store
        self.primary = cfg["primary"]
        self.log: Dict[str, List[Dict[str, np.ndarray]]] = {
            t: [] for t in cfg["tables"]}
        self.rows = {t: 0 for t in cfg["tables"]}

    def ingest(self, table: str, cols: Dict[str, np.ndarray]) -> None:
        if table == self.primary:
            self.store.ingest(cols)
        else:
            self.store.ingest_table(table, cols)
        self.log[table].append(cols)
        self.rows[table] += len(cols["ts"])

    def router(self):
        from repro.serve.router import ShardRouter
        from repro.serve.service import BatchScheduler

        s = self.cfg["scheduler"]
        return ShardRouter(self.svc, BatchScheduler(
            buckets=s["buckets"], max_batch=s["max_batch"],
            max_wait_us=s["max_wait_us"]), ingest=False)


def _mesh(devices, num_shards):
    from repro.core.shard import make_shard_mesh

    return make_shard_mesh(num_shards, devices)


def _read_row(cols, i, names):
    return {c: cols[c][i] for c in names}


def warm_queries(dep: Deployment, reads: Dict[str, np.ndarray]) -> None:
    """Serve batches at every scheduler bucket (read-only) so every query
    shape the window can pop is compiled and loaded: one of the window's
    own rows, and one of a single key, which a sharded store routes to
    one shard (its re-dispatch at full capacity when a shard overflows)."""
    names = [c for c in reads if c != "due"]
    n = len(reads["due"])
    for b in dep.cfg["scheduler"]["buckets"]:
        for rows in (np.arange(b) % n, np.zeros(b, np.int64)):
            r = dep.router()
            for i in rows:
                r.submit(_read_row(reads, i, names), now_us=0)
            out = r.pump(now_us=0, flush=True)
            if out is None or len(next(iter(out.values()))) != b:
                raise Fail(f"warm-up batch of {b} rows was not served whole")


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


class Window:
    """What the window did, in host-clock seconds from its start."""

    def __init__(self, sched: traffic.Schedule, features, tables):
        n = len(sched.reads["due"])
        self.read_due = sched.reads["due"]
        self.read_pump = np.full(n, np.nan)   # start of the serving pump
        self.read_done = np.full(n, np.nan)   # answer in host memory
        # rows of each table ingested before the serving pump
        self.read_cut = {t: np.zeros(n, np.int64) for t in tables}
        self.answers = {f: np.full(n, np.nan, np.float32) for f in features}
        self.write_done = {t: np.full(len(w["due"]), np.nan)
                           for t, w in sched.writes.items()}
        self.pumps = []     # (start, end, rows)
        self.ingests = []   # (table, start, end, rows, keys, segments)


def run_window(dep: Deployment, sched: traffic.Schedule, seconds: float,
               poll_s: float, annotate: Callable,
               at_end: Callable[[], None]) -> Window:
    """Drive the open loop for ``seconds``, call ``at_end``, then drain
    what came due in the window."""
    cfg = dep.cfg
    router = dep.router()
    features = list(dep.svc.view.features)
    win = Window(sched, features, cfg["tables"])
    names = [c for c in sched.reads if c != "due"]
    reads = sched.reads
    due_us = np.floor(reads["due"] * 1e6).astype(np.int64)
    nr = len(due_us)
    wnext = {t: 0 for t in sched.writes}
    fifo: List[int] = []
    submitted = 0
    max_rows = int(cfg["ingest_max_rows"])
    bucket_s = int(cfg["store"]["bucket_size"])
    clock = time.perf_counter
    t0 = clock()

    def deliver(now_rel):
        for table, w in sched.writes.items():
            hi = int(np.searchsorted(w["due"], now_rel, "right"))
            key = cfg["tables"][table]["key"]
            while wnext[table] < hi:
                lo = wnext[table]
                top = min(hi, lo + max_rows)
                with annotate("featbench.deliver_writes"):
                    s = clock()
                    idx = np.arange(lo, top)
                    cols = {c: v[idx] for c, v in w.items() if c != "due"}
                    order = np.lexsort((cols["ts"], cols[key]))
                    cols = {c: v[order] for c, v in cols.items()}
                    with annotate("featbench.ingest"):
                        dep.ingest(table, cols)
                    e = clock()
                win.write_done[table][idx] = e - t0
                k = cols[key].astype(np.int64)
                win.ingests.append((
                    table, s - t0, e - t0, len(idx), len(np.unique(k)),
                    len(np.unique(k << 32 | cols["ts"] // bucket_s))))
                wnext[table] = top

    def serve(now_rel, flush=False):
        s = clock()
        with annotate("featbench.pump"):
            out = router.pump(now_us=int(now_rel * 1e6), flush=flush)
        if out is None:
            return False
        e = clock()
        n = len(next(iter(out.values())))
        ids = np.asarray(fifo[:n])
        del fifo[:n]
        for f, v in out.items():
            win.answers[f][ids] = v
        win.read_pump[ids] = s - t0
        win.read_done[ids] = e - t0
        for t in cfg["tables"]:
            win.read_cut[t][ids] = dep.rows[t]
        win.pumps.append((s - t0, e - t0, n))
        return True

    def submit_until(now_rel):
        nonlocal submitted
        hi = int(np.searchsorted(reads["due"], now_rel, "right"))
        for i in range(submitted, hi):
            router.submit(_read_row(reads, i, names), now_us=int(due_us[i]))
            fifo.append(i)
        submitted = max(submitted, hi)

    next_poll = poll_s
    while True:
        now = clock() - t0
        if now >= seconds:
            break
        submit_until(now)
        if now >= next_poll:
            deliver(now)
            next_poll = (np.floor(now / poll_s) + 1) * poll_s
        if not serve(now):
            nxt = min(next_poll, seconds)
            if submitted < nr:
                nxt = min(nxt, reads["due"][submitted])
            wait = router.scheduler.oldest_wait_us(now_us=int(now * 1e6))
            if wait is not None:
                nxt = min(nxt, now + (cfg["scheduler"]["max_wait_us"]
                                      - wait) / 1e6)
            gap = nxt - (clock() - t0)
            if gap > 2e-4:
                time.sleep(gap - 1e-4)
    win.end = clock() - t0
    at_end()
    # what came due in the window and was not yet served or ingested
    submit_until(seconds)
    while clock() - t0 < seconds + DRAIN_LIMIT_S:
        now = clock() - t0
        deliver(seconds)
        if not serve(now, flush=True):
            break
    return win


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def _pct(x, q):
    return float(np.percentile(x, q))


def run(cell: dict, seed: int, seconds: float, trace: bool, *, devices,
        t_proc: float, peaks: Optional[dict] = None,
        cfg_override: Optional[dict] = None,
        mix_override: Optional[dict] = None,
        control: bool = False,
        on_window: Optional[Callable[["Window"], None]] = None) -> dict:
    """Run one cell once and return the result object.  ``cfg_override``
    and ``mix_override`` replace keys of the data files (CPU rehearsal at
    a tiny size, a rate sweep); ``control`` also returns the control's
    readings; ``on_window`` is handed the window's log."""
    import jax

    cfg = traffic.load("configs", cell["config"])
    mix = traffic.load("traffic", cell["traffic"])
    cfg.update(cfg_override or {})
    mix.update(mix_override or {})
    ref_mod = load_module(
        os.path.join(HERE, "configs", f"{cell['config']}_ref.py"),
        f"ref_{cell['config']}")

    rng = np.random.default_rng(seed)
    hist = traffic.history(rng, cfg, mix["keys"])
    static = traffic.static_tables(rng, cfg)
    sched = traffic.schedule(rng, cfg, mix, seconds)

    t_data = time.perf_counter()
    dep = Deployment(cfg, devices)
    for name, cols in static.items():
        dep.ingest(name, cols)
    for name, cols in hist:
        dep.ingest(name, cols)
    jax.block_until_ready(dep.store.state)
    t_hist = time.perf_counter()
    warm_queries(dep, sched.reads)
    jax.block_until_ready(dep.store.state)
    setup_parts = {"start_and_data_s": t_data - t_proc,
                   "history_s": t_hist - t_data,
                   "warm_queries_s": time.perf_counter() - t_hist}

    from repro.obs import get_telemetry, reset_telemetry

    counter = CompileCounter()
    tdir = None
    annotate = contextlib.nullcontext
    if trace:
        annotate = jax.profiler.TraceAnnotation
        tdir = tempfile.mkdtemp(prefix="featbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tdir, profiler_options=opts)
    snap = {}

    def at_end():
        counter.on = False
        snap.update(get_telemetry().metrics.snapshot())
        if trace:
            jax.profiler.stop_trace()

    # the set-up's objects (the history log, JAX's caches of the programs
    # warmed) stay for the whole run: kept out of the collector's full
    # passes, which would otherwise stall the window for 100s of ms
    gc.collect()
    gc.freeze()
    reset_telemetry()
    counter.on = True
    t_window = time.perf_counter()
    setup_s = t_window - t_proc
    win = run_window(dep, sched, seconds, float(mix["poll_interval_ms"]) / 1e3,
                     annotate, at_end)
    gc.unfreeze()
    if on_window is not None:
        on_window(win)
    mem = [d.memory_stats() or {} for d in devices]
    peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(dep.store.state))
    fill = state_fill(dep.store.state, int(cfg["store"]["capacity"]))
    log = dep.log
    lanes = {cfg["primary"]: int(dep.store.num_lanes)}
    features = list(dep.svc.view.features)
    dep.store.state = None
    del dep
    gc.collect()

    # -- end-to-end metrics --------------------------------------------------
    end = seconds
    lat = (win.read_done - win.read_due) * 1e3
    fresh = []
    done_in = int(np.sum(win.read_done <= end))
    failed = int(np.sum(np.isnan(win.read_done)))
    for t, w in sched.writes.items():
        d = win.write_done[t]
        fresh.append((d - w["due"]) * 1e3)
        done_in += int(np.sum(d <= end))
        failed += int(np.sum(np.isnan(d)))
    fresh = np.concatenate(fresh) if fresh else np.zeros(0)
    # a failed operation misses every latency: it counts at the drain limit
    miss = (seconds + DRAIN_LIMIT_S) * 1e3
    lat = np.where(np.isnan(lat), miss, lat)
    fresh = np.where(np.isnan(fresh), miss, fresh)
    e2e = {
        "request_p50_ms": (_pct(lat, 50), "ms"),
        "request_p95_ms": (_pct(lat, 95), "ms"),
        "freshness_p95_ms": (_pct(fresh, 95), "ms"),
        "ops_per_s": (done_in / seconds, "ops/s"),
        "setup_s": (setup_s, "s"),
    }

    # -- the check -----------------------------------------------------------
    served = ~np.isnan(win.read_done)
    got = {f: win.answers[f][served] for f in features}
    req = {c: v[served] for c, v in sched.reads.items()}
    req["key"] = req[cfg["tables"][cfg["primary"]]["key"]]
    cut = {t: v[served] for t, v in win.read_cut.items()}
    want = check.reference(ref_mod, cfg, log, req, cut)
    checks = check.compare(got, want, ref_mod.EXACT, ref_mod.SQUARED,
                           cfg["limits"])
    readings = None
    if control:
        low = check.reference(ref_mod, cfg, check.as_bf16(log),
                              check.as_bf16(req), cut)
        readings = {
            "program": {k: v["value"] for k, v in checks.items()},
            "control": {k: v["value"] for k, v in
                        check.compare(low, want, ref_mod.EXACT,
                                      ref_mod.SQUARED, cfg["limits"]).items()},
        }
    # an answer that never came is as wrong as a wrong one
    correct = failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    result = {"correct": bool(correct), "attempted": sched.attempted,
              "failed": failed}
    if trace:
        import xplane

        trace_sum = xplane.reduce_dir(tdir, [d.id for d in devices])
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = trace_sum["busy_s"]
        device["window_s"] = trace_sum["window_s"]
        ctx = {
            "cfg": cfg, "mix": mix, "cell": cell, "win": win, "sched": sched,
            "telemetry": snap, "trace": trace_sum, "peaks": peaks,
            "seconds": seconds, "lanes": lanes,
        }
        result["metrics"] = per_layer(cell, ctx)
        result["breakdown"] = trace_sum["breakdown"]
    else:
        result["metrics"] = {k: {"value": v, "unit": u}
                             for k, (v, u) in e2e.items()}
    result["device"] = device
    result["window_compiles"] = counter.count
    result["state_bytes"] = state_bytes
    result["state_fill"] = fill
    result["setup_parts"] = setup_parts
    sizes = [i[3] for i in win.ingests]
    result["window_batches"] = {
        "pumps": len(win.pumps), "ingests": len(sizes),
        "ingest_rows_max": max(sizes, default=0),
        "pump_rows_max": max((p[2] for p in win.pumps), default=0)}
    result["checks"] = checks
    if readings is not None:
        result["readings"] = readings
    return result


def state_fill(state, capacity: int) -> dict:
    """Share of the primary store's ring slots and (key, bucket) cells that
    hold data after the window."""
    import jax.numpy as jnp

    cur = np.asarray(state.ring.cursor).astype(np.int64)
    return {"ring_slots": float(np.minimum(cur, capacity).sum()
                                / (cur.size * capacity)),
            "bucket_cells": float(jnp.mean(state.bagg.bucket != -1))}


def per_layer(cell: dict, ctx: dict) -> dict:
    """Every per-layer metric of BENCHMARK.json that applies to this cell,
    each read by ``featbench/metrics/<name>.py``; a reader that finds
    nothing returns None and the metric is left out."""
    out = {}
    for m in benchmark()["per_layer"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        mod = load_module(os.path.join(HERE, "metrics", f"{m['name']}.py"),
                          "metric_" + m["name"].replace(".", "_"))
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
        elif "workloads" in m:
            # listed for this cell, so its absence is a fault to see
            print(f"featbench: per-layer metric {m['name']} found nothing "
                  "to read", file=sys.stderr)
    return out


def chips(cell: dict):
    """The TPU chips a cell asks for and their peaks, with the persistent
    compile cache on and the program importable; ``Fail`` without them."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Fail(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < int(cell["chips"]):
        raise Fail(f"cell asks for {cell['chips']} chips, JAX sees "
                   f"{len(devs)}")
    peaks = load_peaks(devs[0].device_kind)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    enable_compile_cache()
    return devs[: int(cell["chips"])], peaks


def main(args, t_proc: float) -> int:
    try:
        cell = find_cell(benchmark(), args.workload)
        devices, peaks = chips(cell)
        result = run(cell, args.seed, float(args.seconds), bool(args.trace),
                     devices=devices, t_proc=t_proc, peaks=peaks)
    except Fail as e:
        print(f"featbench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0
