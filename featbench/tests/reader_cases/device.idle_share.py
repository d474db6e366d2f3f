"""0.25 s busy in a 1-s traced window: idle 75% of it."""

CONTEXT = {"trace": {"busy_s": 0.25, "window_s": 1.0}}
WANT = 75.0
