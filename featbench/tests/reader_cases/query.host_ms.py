"""3 + 6 + 3 ms of one-chip query host spans and 2 + 1 ms of the sharded
store's routing and scatter, over 3 ``query.compute`` and 2
``route.device`` batches: 3 ms a batch.  Without the host spans (an older
program) the reader finds nothing."""

DEVICE = [
    {"labels": {"name": "query.compute", "kind": "device"},
     "sum": 0.06, "count": 3},
    {"labels": {"name": "route.device", "kind": "device"},
     "sum": 0.08, "count": 2}]
HOST = [
    {"labels": {"name": "query.prepare", "kind": "host"},
     "sum": 0.003, "count": 3},
    {"labels": {"name": "query.finish", "kind": "host"},
     "sum": 0.006, "count": 3},
    {"labels": {"name": "request.fetch", "kind": "host"},
     "sum": 0.003, "count": 3},
    {"labels": {"name": "query.route", "kind": "host"},
     "sum": 0.002, "count": 2},
    {"labels": {"name": "query.scatter", "kind": "host"},
     "sum": 0.001, "count": 2}]
CONTEXT = {"telemetry": {"span_seconds": {"series": DEVICE + HOST}}}
WANT = 3.0
VARIANTS = [({"telemetry": {"span_seconds": {"series": DEVICE}}}, None)]
