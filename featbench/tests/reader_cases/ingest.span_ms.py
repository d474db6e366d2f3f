"""Two fenced ``ingest`` spans of 10 ms together: 5 ms each."""

CONTEXT = {"telemetry": {"span_seconds": {"series": [
    {"labels": {"name": "ingest", "kind": "device"},
     "sum": 0.01, "count": 2}]}}}
WANT = 5.0
