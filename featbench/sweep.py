"""Find a cell's knee: the highest offered rate its queue keeps up with.

    python3 featbench/sweep.py --workload <cell> --rates 4000,7000,11000 --seconds 10 --seed <n>

One run of the cell per rate (one process, in turn), with the mix's
``rate_ops_per_s`` replaced.  A rate is sustained when the window
completes at least 97% of what it offered, no more than 1% of the
operations are still pending at its end, and the median queue wait of
the reads due in its last quarter is at most twice that of its first
quarter plus 5 ms (the queue does not grow).  Prints one JSON line per
rate, then ``{"knee": ..., "rate_4_5": ...}``: the highest sustained
rate and 4/5 of it, rounded to 100 ops/s, for the mix's data file.
"""

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()
    import numpy as np

    import harness

    try:
        cell = harness.find_cell(harness.benchmark(), args.workload)
        devices, peaks = harness.chips(cell)
    except harness.Fail as e:
        print(f"featbench: {e}", file=sys.stderr)
        return 2
    t, sustained, secs = T_PROC, [], args.seconds
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        got = {}
        r = harness.run(cell, args.seed + i, secs, False, devices=devices,
                        t_proc=t, peaks=peaks,
                        mix_override={"rate_ops_per_s": rate},
                        on_window=lambda w: got.update(w=w))
        t = time.perf_counter()
        w = got["w"]
        wait = (w.read_pump - w.read_due) * 1e3
        q1, q3 = np.quantile(w.read_due, [0.25, 0.75])
        early = float(np.nanmedian(wait[w.read_due < q1]))
        late = float(np.nanmedian(wait[w.read_due > q3]))
        pending = int(np.sum(~(w.read_done <= secs))) + sum(
            int(np.sum(~(d <= secs))) for d in w.write_done.values())
        m = {k: v["value"] for k, v in r["metrics"].items()}
        ok = (m["ops_per_s"] >= 0.97 * rate and pending <= 0.01 * rate * secs
              and late <= 2 * early + 5)
        if ok:
            sustained.append(rate)
        print(json.dumps({"rate": rate, "sustained": ok, "correct": r["correct"],
                          "metrics": m, "wait_early_ms": early,
                          "wait_late_ms": late, "pending_at_end": pending,
                          "pumps": len(w.pumps), "ingests": len(w.ingests),
                          "window_compiles": r["window_compiles"],
                          "device": r["device"]}), flush=True)
    knee = max(sustained) if sustained else None
    rate = None if knee is None else int(round(0.8 * knee / 100.0) * 100)
    print(json.dumps({"knee": knee, "rate_4_5": rate}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
