"""Plain reference of ``fraud_view`` (FeatInsight sec. 3.3), as written in
``repro.scenarios`` and in SQL::

    SUM/AVG/STDDEV/COUNT(amount) OVER 1 h, SUM/MAX(amount) OVER 6 h,
    COUNT(amount) OVER ROWS 50,
    COUNT(amount > 100) OVER 1 h / (1 + COUNT(amount) OVER 1 h)

COUNT counts rows (a boolean argument is never NULL).  Window semantics
are those of ``featbench/reference.py``.
"""

import reference as R

# answers that are exact in float32: compared bit for bit
EXACT = ("tx_count_1h", "tx_count_50", "amt_max_6h")
# standard deviations: compared as variances at the scale of the window's
# mean square, returned as "<feature>.meansq" (see check.py)
SQUARED = ("amt_std_1h",)


def features(tables, req, cutoff, cfg):
    st = cfg["store"]
    tx = tables["transactions"]
    c = cutoff["transactions"]
    amt = req["amount"]

    def rng(span):
        return R.with_row(
            tx.range_window("amount", req, c, span, st["bucket_size"]), amt)

    w1h, w6h = rng(3600), rng(21600)
    r50 = R.with_row(tx.rows_window("amount", req, c, 50), amt)
    return {
        "amt_sum_1h": w1h["sum"],
        "amt_mean_1h": R.mean(w1h),
        "amt_std_1h": R.std(w1h),
        "amt_std_1h.meansq": w1h["sumsq"] / w1h["count"],
        "tx_count_1h": w1h["count"],
        "amt_sum_6h": w6h["sum"],
        "amt_max_6h": w6h["max"],
        "tx_count_50": r50["count"],
        "big_ratio_1h": w1h["count"] / (1.0 + w1h["count"]),
    }
