"""Three runs of the one-chip preagg query program, 30 ms of device time
together: 10 ms a run.  Without it, two runs of a program named after
``_route_query_pure``, 40 ms together: 20 ms a run.  A trace with neither
reads nothing."""

CONTEXT = {"trace": {"modules": {
    "jit__query_pure_preagg": (0.03, 3, ""),
    "jit__route_query_pure": (0.04, 2, "")}}}
WANT = 10.0
VARIANTS = [
    ({"trace": {"modules": {"jit__route_query_pure": (0.04, 2, "")}}}, 20.0),
    ({"trace": {"modules": {}}}, None),
]
