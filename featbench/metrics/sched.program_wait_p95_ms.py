"""95th percentile of a read's wait in the scheduler queue, from submission
to the pop of its batch (serve/service.py ``BatchScheduler``), as the
program counts it in ``queue_wait_seconds``, in ms: read from the
histogram's bucket counts over the window, linear within the bucket that
holds it (its last bucket ends at the largest wait).  The program-side
twin of ``sched.queue_wait_p95_ms``, which the harness times.

The histogram's bounds are 10 per decade; where the bucket that holds
the percentile is wider than a tenth of a decade (a program with coarser
bounds), the reading would say more about the bounds than about the
waits, and the reader finds nothing."""

WIDEST = 10 ** 0.1 * (1 + 1e-9)


def read(ctx):
    series = ctx["telemetry"].get("queue_wait_seconds", {}).get("series", [])
    if not series:
        return None
    bounds = [b for b, _ in series[0]["buckets"]]
    counts = [sum(s["buckets"][i][1] for s in series)
              for i in range(len(bounds))]
    rank = 0.95 * sum(counts)
    below = 0.0
    for i, c in enumerate(counts):
        if c and below + c >= rank:
            lo = bounds[i - 1] if i else 0.0
            if bounds[i] == "+Inf":
                hi = max(s["max"] for s in series)
            elif lo and bounds[i] / lo > WIDEST:
                return None
            else:
                hi = bounds[i]
            return 1e3 * (lo + (hi - lo) * (rank - below) / c)
        below += c
    return None
