"""Mean of the program's ``ingest.prepare`` span per micro-batch, in ms
(core/online.py ingest: key and timestamp uploads, window-argument lanes,
the timestamps back to the host): the part of a write's freshness clock
that falls before the fenced ``ingest`` span."""

import readers


def read(ctx):
    return readers.span_ms(ctx["telemetry"], "ingest.prepare")
