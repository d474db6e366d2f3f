"""Mean of the program's fenced ``route.device`` span per batch (fused
route and query of the sharded store, core/shard.py)."""

import readers


def read(ctx):
    return readers.span_ms(ctx["telemetry"], "route.device")
