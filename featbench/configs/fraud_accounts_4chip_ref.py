"""Plain reference of ``sharded_view`` over ``MULTITABLE_DB``, as written in
``repro.scenarios`` and in SQL::

    LAST JOIN accounts.credit_limit (default 1000) ON account,
    LAST JOIN merchants.avg_ticket (default 50) ON merchant,
    SUM/COUNT(amount) OVER 1 h WINDOW UNION wires,
    AVG(amount) OVER 1 h,
    SUM(amount) OVER 1 h UNION wires / credit_limit

Where a key lives does not change an answer, so the reference knows no
shards.  Window semantics are those of ``featbench/reference.py``.
"""

import reference as R

EXACT = ("credit_limit", "merchant_ticket", "outflow_cnt_1h")
SQUARED = ()


def features(tables, req, cutoff, cfg):
    st = cfg["store"]
    tx, wires = tables["transactions"], tables["wires"]
    amt, ts = req["amount"], req["ts"]
    own = R.with_row(tx.range_window("amount", req, cutoff["transactions"],
                                     3600, st["bucket_size"]), amt)
    union = R.combine(own, wires.ring_range("amount", req, cutoff["wires"],
                                            3600))
    credit = tables["accounts"].last_join(
        "credit_limit", req["key"], ts, cutoff["accounts"], 1000.0)
    ticket = tables["merchants"].last_join(
        "avg_ticket", req["merchant"], ts, cutoff["merchants"], 50.0)
    return {
        "credit_limit": credit,
        "merchant_ticket": ticket,
        "outflow_1h": union["sum"],
        "outflow_cnt_1h": union["count"],
        "spend_mean_1h": R.mean(own),
        "utilization": union["sum"] / credit,
    }
