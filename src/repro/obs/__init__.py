"""Plane-wide observability: one clock, one metric registry, one tracer.

Public surface::

    from repro.obs import (
        Clock, FakeClock,               # the plane's single time source
        Telemetry, get_telemetry,       # process-wide bundle
        set_telemetry, reset_telemetry, use_telemetry,
        MetricRegistry, Counter, Gauge, Histogram,
        Tracer, Span,
    )

``repro.obs.report`` renders a telemetry snapshot as a markdown
dashboard (``python -m repro.obs.report``); ``repro.obs.check`` holds the
CI gates (snapshot-schema golden set + instrumentation overhead bound).
"""

from repro.obs.telemetry import (
    DEFAULT_BUCKETS_S,
    QUEUE_WAIT_BUCKETS_S,
    Clock,
    Counter,
    FakeClock,
    Gauge,
    Histogram,
    MetricCardinalityError,
    MetricRegistry,
    Telemetry,
    get_telemetry,
    reset_telemetry,
    set_telemetry,
    use_telemetry,
)
from repro.obs.tracing import Span, Tracer

__all__ = [
    "DEFAULT_BUCKETS_S",
    "QUEUE_WAIT_BUCKETS_S",
    "Clock",
    "Counter",
    "FakeClock",
    "Gauge",
    "Histogram",
    "MetricCardinalityError",
    "MetricRegistry",
    "Telemetry",
    "get_telemetry",
    "reset_telemetry",
    "set_telemetry",
    "use_telemetry",
    "Span",
    "Tracer",
]
