"""200 ms of ``router.pump`` over 3 pumps, less the 60 ms of
``query.compute`` and the 80 ms of ``route.device`` inside them: 20 ms of
host time a pump.  Without ``router.pump`` spans (an older program) the
reader finds nothing."""

DEVICE = [
    {"labels": {"name": "query.compute", "kind": "device"},
     "sum": 0.06, "count": 3},
    {"labels": {"name": "route.device", "kind": "device"},
     "sum": 0.08, "count": 2}]
CONTEXT = {"telemetry": {"span_seconds": {"series": DEVICE + [
    {"labels": {"name": "router.pump", "kind": "host"},
     "sum": 0.2, "count": 3}]}}}
WANT = 20.0
VARIANTS = [({"telemetry": {"span_seconds": {"series": DEVICE}}}, None)]
