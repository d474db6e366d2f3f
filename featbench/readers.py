"""Shared arithmetic of the per-layer metric readers (``metrics/<name>.py``)."""

from __future__ import annotations

import roofline
import xplane


def span_ms(snapshot: dict, name: str):
    """Mean of the program's ``span_seconds{name=...}`` over the window, in
    ms (the histogram's exact sum and count), or None where it never ran."""
    total, count = 0.0, 0.0
    for s in snapshot.get("span_seconds", {}).get("series", []):
        if s["labels"].get("name") == name:
            total += s["sum"]
            count += s["count"]
    return 1e3 * total / count if count else None


def kernel_roofline(ctx: dict, kernel: str, needed_bytes: float):
    """A kernel's share of the HBM roofline over the traced window, in %."""
    sec, _ = xplane.seconds_matching(ctx["trace"]["ops"], kernel)
    return roofline.roofline_share(
        needed_bytes, sec, ctx["peaks"]["hbm_bytes_per_s"])
