"""Telemetry series for the per-layer readers of the program's host spans
(``pump.host_ms``, ``query.host_ms``, ``ingest.prepare_ms``) and of its
``queue_wait_seconds`` histogram (``sched.program_wait_p95_ms``), laid
over the readers' test context of ``test_featbench_parts``."""

import copy

HOST_SPANS = [
    {"labels": {"name": "router.pump", "kind": "host"},
     "sum": 0.2, "count": 3},
    {"labels": {"name": "query.prepare", "kind": "host"},
     "sum": 0.003, "count": 3},
    {"labels": {"name": "query.finish", "kind": "host"},
     "sum": 0.006, "count": 3},
    {"labels": {"name": "request.fetch", "kind": "host"},
     "sum": 0.003, "count": 3},
    {"labels": {"name": "query.route", "kind": "host"},
     "sum": 0.002, "count": 2},
    {"labels": {"name": "query.scatter", "kind": "host"},
     "sum": 0.001, "count": 2},
    {"labels": {"name": "ingest.prepare", "kind": "host"},
     "sum": 0.004, "count": 2}]

QUEUE_WAIT = {"series": [
    {"labels": {"service": "fraud"}, "sum": 0.5, "count": 24, "max": 0.08,
     "buckets": [[0.04, 10.0], [0.045, 5.0], [0.05, 8.0], ["+Inf", 1.0]]}]}


def add_program_spans(ctx):
    """``ctx`` with the host spans appended to its ``span_seconds`` and a
    ``queue_wait_seconds`` histogram added (fresh copies each call)."""
    snap = ctx["telemetry"]
    snap["span_seconds"]["series"] += copy.deepcopy(HOST_SPANS)
    snap["queue_wait_seconds"] = copy.deepcopy(QUEUE_WAIT)
    return ctx
