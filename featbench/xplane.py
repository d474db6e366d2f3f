"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Read with ``jax.profiler.ProfileData`` alone.  On a TPU each chip is a
plane ``/device:TPU:<id>``; its line ``XLA Ops`` holds one event per
device operation and its line ``XLA Modules`` one per executable run.  The
host planes hold the harness's ``TraceAnnotation`` spans (``featbench.*``)
on the clock the device events were aligned to.

* busy: the union of the op intervals of a chip, averaged over the chips
  used; ``window_s`` is the traced window, from the first to the last
  harness span.
* ops / modules: device seconds, counts and the first event's string
  stats per op name and per executable (the module's name without its
  ``(...)`` suffix), summed over chips.
* module_ops: the same per op name within each executable; an op belongs
  to the run of a module on its chip that holds the op's start.
* breakdown: the ten ops that took most device time, and the ten longest
  idle gaps of the first chip, each named by the harness span the host was
  in at the gap's middle (``idle`` where it was in none: waiting for
  arrivals).
"""

from __future__ import annotations

import glob
import os
import re
from bisect import bisect_right
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
ANNOTATION = "featbench."


def find_xplane(root: str) -> str:
    hits = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    if len(hits) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {root}, found {hits}")
    return hits[0]


def reduce_dir(root: str, device_ids: Sequence[int]) -> dict:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(find_xplane(root)), device_ids)


def _union(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _events(line):
    for ev in line.events:
        yield ev, ev.name, int(ev.start_ns), int(ev.duration_ns)


def _stat_text(ev) -> str:
    """The event's string stats (its HLO text, op name, kernel name)."""
    try:
        return " ".join(str(v) for _, v in ev.stats if isinstance(v, str))
    except (TypeError, ValueError):
        return ""


def _add(acc: list, ev, d: int) -> None:
    acc[0] += d * 1e-9
    acc[1] += 1
    if not acc[2]:
        acc[2] = _stat_text(ev)


def reduce(profile, device_ids: Sequence[int]) -> dict:
    """See the module docstring.  ``device_ids``: the chips the run used."""
    want = {int(i) for i in device_ids}
    busy_iv: Dict[int, List[Tuple[int, int]]] = {}
    ops: Dict[str, list] = defaultdict(lambda: [0.0, 0, ""])
    modules: Dict[str, list] = defaultdict(lambda: [0.0, 0, ""])
    module_ops: Dict[str, Dict[str, list]] = defaultdict(
        lambda: defaultdict(lambda: [0.0, 0, ""]))
    spans: List[Tuple[int, int, str]] = []
    for plane in profile.planes:
        m = _DEVICE.match(plane.name)
        if m is None:
            for line in plane.lines:
                for _, name, t, d in _events(line):
                    if name.startswith(ANNOTATION):
                        spans.append((t, t + d, name))
            continue
        dev = int(m.group(1))
        if dev not in want:
            continue
        runs: List[Tuple[int, int, str]] = []
        dev_ops = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                iv = busy_iv.setdefault(dev, [])
                for ev, name, t, d in _events(line):
                    iv.append((t, t + d))
                    _add(ops[name], ev, d)
                    dev_ops.append((ev, name, t, d))
            elif line.name == "XLA Modules":
                for ev, name, t, d in _events(line):
                    mod = name.split("(")[0]
                    _add(modules[mod], ev, d)
                    runs.append((t, t + d, mod))
        runs.sort()
        starts = [r[0] for r in runs]
        for ev, name, t, d in dev_ops:
            i = bisect_right(starts, t) - 1
            if i >= 0 and t < runs[i][1]:
                _add(module_ops[runs[i][2]][name], ev, d)
    if not busy_iv:
        raise RuntimeError("the trace holds no device operation")
    if spans:
        w0 = min(s[0] for s in spans)
        w1 = max(s[1] for s in spans)
    else:
        w0 = min(a for iv in busy_iv.values() for a, _ in iv)
        w1 = max(b for iv in busy_iv.values() for _, b in iv)
    merged = {d: _union([(max(a, w0), min(b, w1)) for a, b in iv
                         if b > w0 and a < w1])
              for d, iv in busy_iv.items()}
    busy = sum(sum(b - a for a, b in iv) for iv in merged.values())
    busy_s = busy / len(want) * 1e-9
    first = merged.get(min(want), [])
    gaps = []
    edges = [w0] + [x for iv in first for x in iv] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((b - a, a, b))
    gaps.sort(reverse=True)
    spans.sort()
    idle_gaps = []
    for d, a, b in gaps[:10]:
        mid = (a + b) // 2
        inner = [s for s in spans if s[0] <= mid <= s[1]]
        # the innermost span the host was in
        name = min(inner, key=lambda s: s[1] - s[0])[2] if inner else "idle"
        idle_gaps.append([name, d * 1e-9])
    top = sorted(ops.items(), key=lambda kv: -kv[1][0])[:10]
    return {
        "busy_s": busy_s,
        "window_s": (w1 - w0) * 1e-9,
        "ops": {k: tuple(v) for k, v in ops.items()},
        "modules": {k: tuple(v) for k, v in modules.items()},
        "module_ops": {m: {k: tuple(v) for k, v in t.items()}
                       for m, t in module_ops.items()},
        "breakdown": {
            "device_ops": [[k, v[0]] for k, v in top],
            "idle_gaps": idle_gaps,
        },
    }


def seconds_matching(table: Dict[str, tuple], pattern: str) -> Tuple[float, int]:
    """Summed seconds and count of the entries (ops or modules) whose name,
    or whose string stats (HLO text, kernel name), contain ``pattern``."""
    s, n = 0.0, 0
    for k, (sec, cnt, text) in table.items():
        if pattern in k or pattern in text:
            s += sec
            n += cnt
    return s, n
