"""Share of the HBM roofline of the Pallas ``route_rank`` kernel of the
sharded store's fused route and query, over the traced window: the bytes
the window's request batches need (``roofline.route_rank_bytes``) over
peak bandwidth, against the kernel's device time summed over the chips.
The kernel runs replicated, every chip ranking the whole batch, so a
batch's bytes count once a chip."""

import readers
import roofline

KERNEL = "route_rank"


def read(ctx):
    shards = int(ctx["cfg"]["store"].get("num_shards", 1))
    end = ctx["win"].end
    need = shards * sum(roofline.route_rank_bytes(n, shards)
                        for a, b, n in ctx["win"].pumps if b <= end)
    return readers.kernel_roofline(ctx, KERNEL, need)
