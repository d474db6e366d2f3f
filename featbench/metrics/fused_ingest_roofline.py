"""Share of the HBM roofline of the Pallas ``fused_ingest`` kernel over the
traced window: the bytes the window's ingest batches of the primary table
need (``roofline.fused_ingest_bytes``) over peak bandwidth, against the
kernel's device time, summed over the chips used.

The ``pallas_call`` carries no name of its own, and XLA names its op after
what holds it: ``%_ingest_pure.1`` on one chip, ``%vmap__.1`` under the
sharded store's per-shard vmap on four.  So the kernel is read as the
Mosaic custom call (``custom_call_target="tpu_custom_call"``) that runs
inside the jitted ingest program, ``jit__ingest_pure``."""

import roofline
import xplane

PROGRAM = "jit__ingest_pure"
KERNEL = 'custom_call_target="tpu_custom_call"'


def read(ctx):
    lanes = ctx["lanes"]
    end = ctx["win"].end
    need = sum(roofline.fused_ingest_bytes(rows, segs, lanes[t])
               for t, a, b, rows, keys, segs in ctx["win"].ingests
               if b <= end and t in lanes)
    sec, _ = xplane.seconds_matching(
        ctx["trace"]["module_ops"].get(PROGRAM, {}), KERNEL)
    return roofline.roofline_share(need, sec, ctx["peaks"]["hbm_bytes_per_s"])
