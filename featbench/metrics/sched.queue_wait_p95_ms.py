"""95th percentile of a read's wait from its due time to the start of the
pump that served it (router and BatchScheduler), harness clock, over the
reads served inside the window."""

import numpy as np


def read(ctx):
    win = ctx["win"]
    ok = win.read_pump <= win.end
    if not ok.any():
        return None
    return float(np.percentile((win.read_pump - win.read_due)[ok] * 1e3, 95))
