"""Reads served in the window waited 10, 50 and 10 ms from their due time
to the start of their pump; the read pumped after the window's end does
not count.  Their 95th percentile, linear between ranks, is 46 ms."""

CONTEXT = {"win": {"end": 1.0,
                   "read_due": [0.1, 0.2, 0.3, 0.9],
                   "read_pump": [0.11, 0.25, 0.31, 1.2]}}
WANT = 46.0
