"""Benchmark harness — one module per paper table/claim.

  python -m benchmarks.run            # all feature/system benches + roofline
  python -m benchmarks.run --only feature_latency
  python -m benchmarks.run --smoke    # CI: tiny N, one rep, no roofline

Multi-device CPU (the shard bench wants 8 shards = 8 devices):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 python -m benchmarks.run
"""

from __future__ import annotations

import argparse
import time
import traceback

from benchmarks import common
from benchmarks.common import emit, header
from repro.compile_cache import enable_compile_cache

BENCHES = [
    "feature_latency",   # §3.3 fraud: naive vs tuned vs featinsight
    "window_agg",        # §2 pre-aggregation vs window size + kernel check
    "fold",              # kernel roofline: XLA vs Pallas fold + fused ingest
    "ingest",            # §3.2 millisecond updates / 720M orders/day
    "wide_view",         # Fig. 4: 784-feature banking view
    "deploy",            # §3.2 one-click deployment pipeline
    "consistency",       # §2 offline/online verification
    "signature",         # §1 trillion-dim signatures
    "join",              # §1 multi-table plane: LAST JOIN + WINDOW UNION
    "shard",             # sharded serving plane: throughput vs shard count
    "stress",            # generated-plane scale: N views deploy/QPS/lanes
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--skip-roofline", action="store_true")
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI mode: tiny sizes, one rep per timing, skip roofline",
    )
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        common.set_smoke(True)

    header()
    failures = []
    for name in BENCHES:
        if args.only and name != args.only:
            continue
        mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
        t0 = time.perf_counter()
        try:
            mod.run()
            emit(name, "bench_wall_s", time.perf_counter() - t0, "s")
        except Exception as e:  # keep the harness running
            failures.append(name)
            emit(name, "FAILED", 0, "", str(e)[:120].replace(",", ";"))
            traceback.print_exc()

    if not args.skip_roofline and not args.only and not args.smoke:
        from benchmarks import roofline
        roofline.run()

    if failures:
        raise SystemExit(f"benchmarks failed: {failures}")


if __name__ == "__main__":
    main()
