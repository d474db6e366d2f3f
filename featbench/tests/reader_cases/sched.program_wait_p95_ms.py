"""24 waits in the program's ``queue_wait_seconds``: rank 0.95 x 24 = 22.8
falls in the bucket (45, 50] ms, which holds waits 16 to 23, so it reads
45 + 5 x (22.8 - 15) / 8 ms.  A rank in the overflow bucket ends at the
largest wait, 80 ms; a bucket wider than a tenth of a decade (coarser
bounds) reads nothing, and so does a program without the histogram."""


def _waits(buckets):
    return {"telemetry": {"queue_wait_seconds": {"series": [
        {"labels": {"service": "fraud"}, "sum": 0.5, "count": 24,
         "max": 0.08, "buckets": buckets}]}}}


CONTEXT = _waits([[0.04, 10.0], [0.045, 5.0], [0.05, 8.0], ["+Inf", 1.0]])
WANT = 45 + 5 * (22.8 - 15) / 8
VARIANTS = [
    (_waits([[0.04, 0.0], [0.045, 0.0], [0.05, 0.0], ["+Inf", 2.0]]),
     50 + 30 * 0.95),
    (_waits([[0.01, 0.0], [0.03, 0.0], [0.1, 2.0], ["+Inf", 0.0]]), None),
    ({"telemetry": {}}, None),
]
