"""Device time per run of the query executable, from the trace: the
one-chip preagg program, or the sharded store's fused route and query."""

import xplane

PATTERNS = ("_query_pure_preagg", "_route_query_pure")


def read(ctx):
    for p in PATTERNS:
        sec, n = xplane.seconds_matching(ctx["trace"]["modules"], p)
        if n:
            return 1e3 * sec / n
    return None
