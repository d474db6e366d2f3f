"""Two ``ingest.prepare`` spans of 4 ms together: 2 ms each.  Without the
span (an older program) the reader finds nothing."""

CONTEXT = {"telemetry": {"span_seconds": {"series": [
    {"labels": {"name": "ingest.prepare", "kind": "host"},
     "sum": 0.004, "count": 2},
    {"labels": {"name": "ingest", "kind": "device"},
     "sum": 0.01, "count": 2}]}}}
WANT = 2.0
VARIANTS = [
    ({"telemetry": {"span_seconds": {"series": [
        {"labels": {"name": "ingest", "kind": "device"},
         "sum": 0.01, "count": 2}]}}}, None),
]
