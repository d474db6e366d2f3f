"""Two fenced ``route.device`` spans of the sharded store's fused route and
query, 80 ms together: 40 ms each."""

CONFIG = "fraud_accounts_4chip"

CONTEXT = {"telemetry": {"span_seconds": {"series": [
    {"labels": {"name": "route.device", "kind": "device"},
     "sum": 0.08, "count": 2}]}}}
WANT = 40.0
