"""Host time of the query layer per query batch, in ms: the program's
``query.prepare`` (request arrays, lanes), ``query.finish`` (query
counters, post-expressions) and ``request.fetch`` (answers to host
memory) spans, plus ``query.route`` and ``query.scatter`` on the sharded
store, over the query batches: one ``query.compute`` each, or one
``route.device`` on the sharded store."""

HOST = ("query.prepare", "query.finish", "request.fetch", "query.route",
        "query.scatter")
BATCH = ("query.compute", "route.device")


def _sum_count(snapshot, names):
    total, count = 0.0, 0.0
    for s in snapshot.get("span_seconds", {}).get("series", []):
        if s["labels"].get("name") in names:
            total += s["sum"]
            count += s["count"]
    return total, count


def read(ctx):
    snap = ctx["telemetry"]
    host, spans = _sum_count(snap, HOST)
    _, batches = _sum_count(snap, BATCH)
    if not spans or not batches:
        return None
    return 1e3 * host / batches
