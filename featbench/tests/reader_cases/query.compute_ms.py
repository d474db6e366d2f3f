"""Three fenced ``query.compute`` spans of 60 ms together: 20 ms each."""

CONTEXT = {"telemetry": {"span_seconds": {"series": [
    {"labels": {"name": "query.compute", "kind": "device"},
     "sum": 0.06, "count": 3}]}}}
WANT = 20.0
