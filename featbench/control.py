"""Readings that set a cell's limits: the program's and the control's.

    python3 featbench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, one whole run of the cell at its own size and load (one
process, seeds in turn), then two comparisons with the reference: of the
answers the program served, and of the control's (the reference with
every stored and request value held as bfloat16, in the program's place).
Prints one JSON line per seed, then one with the largest program reading
and the smallest control reading of each number.  The benchmark's own
runs never run the control.
"""

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
# libtpu would otherwise write its logs under /tmp, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    import harness

    try:
        cell = harness.find_cell(harness.benchmark(), args.workload)
        devices, peaks = harness.chips(cell)
    except harness.Fail as e:
        print(f"featbench: {e}", file=sys.stderr)
        return 2
    low, high = {}, {}
    t = T_PROC
    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.run(cell, seed, args.seconds, False, devices=devices,
                        t_proc=t, peaks=peaks, control=True)
        t = time.perf_counter()
        rd = r["readings"]
        print(json.dumps({"seed": seed, "correct": r["correct"], **rd,
                          "metrics": {k: v["value"]
                                      for k, v in r["metrics"].items()}}),
              flush=True)
        for k, v in rd["program"].items():
            low[k] = max(low.get(k, v), v)
        for k, v in rd["control"].items():
            high[k] = min(high.get(k, v), v)
    print(json.dumps({"program_max": low, "control_min": high}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
