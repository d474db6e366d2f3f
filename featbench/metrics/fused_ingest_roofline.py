"""Share of the HBM roofline of the Pallas ``fused_ingest`` kernel over the
traced window: the bytes the window's ingest batches of the primary table
need (``roofline.fused_ingest_bytes``) over peak bandwidth, against the
kernel's device time.

The ``pallas_call`` carries no name of its own, so on a v5e its op in the
trace is named after the jitted ingest that holds it:
``%_ingest_pure.1 = (s32[131072,256]..., ...) ...``, the one op of that
program that returns the state."""

import readers
import roofline

KERNEL = "%_ingest_pure."


def read(ctx):
    lanes = ctx["lanes"]
    end = ctx["win"].end
    need = sum(roofline.fused_ingest_bytes(rows, segs, lanes[t])
               for t, a, b, rows, keys, segs in ctx["win"].ingests
               if b <= end and t in lanes)
    return readers.kernel_roofline(ctx, KERNEL, need)
