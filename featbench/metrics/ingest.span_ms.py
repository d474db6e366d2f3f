"""Mean of the program's fenced ``ingest`` span per micro-batch
(core/online.py ingest; the sharded store's routed ingest)."""

import readers


def read(ctx):
    return readers.span_ms(ctx["telemetry"], "ingest")
