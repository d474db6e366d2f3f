"""Run one benchmark cell once and print its result as one JSON line.

    python3 featbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` in BENCHMARK.json) names a configuration
(``featbench/configs/<name>.json``) and a traffic mix
(``featbench/traffic/<name>.json``).  With ``--trace 0`` the result holds
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics
read from a profiler trace of the window.  Exits non-zero, printing no
result, where JAX finds no TPU, fewer chips than the cell asks for, or a
device kind missing from ``featbench/peaks.json``.
"""

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
# libtpu would otherwise write its logs under /tmp, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    import harness

    return harness.main(args, T_PROC)


if __name__ == "__main__":
    sys.exit(main())
