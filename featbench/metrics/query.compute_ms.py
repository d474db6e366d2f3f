"""Mean of the program's fenced ``query.compute`` span per batch (one-chip
store, core/online.py)."""

import readers


def read(ctx):
    return readers.span_ms(ctx["telemetry"], "query.compute")
