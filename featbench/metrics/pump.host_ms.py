"""Host time per served pump during which a backlogged chip has nothing to
run, in ms: the program's ``router.pump`` spans (serve/router.py; recorded
only for a pump that pops a batch) less the fenced device spans inside
them (``query.compute`` on one chip, ``route.device`` on the sharded
store), over the number of pumps.

This holds while the router runs with ``ingest=False``, as the harness's
does: then no query or ingest runs outside a pump's request, and no
``ingest`` span falls inside a pump."""

DEVICE = ("query.compute", "route.device")


def _sum_count(snapshot, names):
    total, count = 0.0, 0.0
    for s in snapshot.get("span_seconds", {}).get("series", []):
        if s["labels"].get("name") in names:
            total += s["sum"]
            count += s["count"]
    return total, count


def read(ctx):
    snap = ctx["telemetry"]
    pump, pumps = _sum_count(snap, ("router.pump",))
    if not pumps:
        return None
    device, _ = _sum_count(snap, DEVICE)
    return 1e3 * (pump - device) / pumps
