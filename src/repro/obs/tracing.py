"""Nested request-path spans with explicit device fencing.

JAX dispatch is asynchronous: a jitted call returns device futures, so a
naive ``perf_counter`` pair around it measures *dispatch* cost, not
compute.  A :class:`Span` therefore carries a ``fence()`` method —
``jax.block_until_ready`` on the stage's outputs — so a span that claims
to measure device time provably contains it.  Host-side stages (queue
wait, shard routing, scatter-back) never fence; device stages always do.
That is the whole host/device attribution story, and it is why ROADMAP
item 1's "measured, not assumed" split is now measured.

Spans nest via a stack (``tracer.span(...)`` context managers), and every
completed span *also* folds its duration into the ``span_seconds{name=}``
histogram in the metric registry — dashboards and benchmarks read the
aggregate without walking trees, while tests can assert on the exact tree
shape under a :class:`~repro.obs.telemetry.FakeClock`.

Each enabled span is also a ``jax.profiler.TraceAnnotation`` of its name
(the name only: no attribute formatting), so a profiler trace shows the
span tree on its host plane, on the clock the device events are aligned
to, and an idle gap on the chip can be named by the span the host was in.
With no profiler session the annotation costs one activity check (under
a microsecond on a CPU core).
"""

from __future__ import annotations

import contextlib
from collections import deque
from typing import Any, Deque, Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Span", "Tracer", "SPAN_KINDS"]

SPAN_KINDS = ("host", "device")


class Span:
    """One timed stage of the request path (possibly with children).

    ``kind`` is ``"host"`` or ``"device"``; a device span should call
    :meth:`fence` on the stage's outputs before it closes, so the recorded
    duration includes device execution rather than just async dispatch.
    """

    __slots__ = ("name", "kind", "t0", "t1", "attrs", "children", "fenced")

    def __init__(self, name: str, kind: str, t0: float,
                 attrs: Optional[Dict[str, Any]] = None):
        if kind not in SPAN_KINDS:
            raise ValueError(f"span kind must be one of {SPAN_KINDS}: {kind!r}")
        self.name = name
        self.kind = kind
        self.t0 = t0
        self.t1: Optional[float] = None
        self.attrs: Dict[str, Any] = dict(attrs or {})
        self.children: List["Span"] = []
        self.fenced = False

    @property
    def duration_s(self) -> float:
        return 0.0 if self.t1 is None else self.t1 - self.t0

    def set(self, **attrs: Any) -> "Span":
        self.attrs.update(attrs)
        return self

    def fence(self, *values: Any) -> Any:
        """``jax.block_until_ready`` the stage outputs inside this span, so
        its duration attributes device compute to this stage (and not to
        whatever host code happens to touch the arrays next).  Returns the
        fenced value(s) unchanged; non-array pytrees pass through."""
        import jax

        out = tuple(jax.block_until_ready(v) for v in values)
        self.fenced = True
        return out[0] if len(out) == 1 else out

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "kind": self.kind,
            "t0_s": self.t0,
            "duration_s": self.duration_s,
            "fenced": self.fenced,
            "attrs": dict(self.attrs),
            "children": [c.to_dict() for c in self.children],
        }

    def tree(self, indent: int = 0) -> str:
        """Human-readable nested rendering (used by the report module)."""
        pad = "  " * indent
        mark = "⏚" if self.fenced else "·"
        lines = [
            f"{pad}{self.name} [{self.kind}] {mark} "
            f"{self.duration_s * 1e3:.3f} ms"
            + (f"  {self.attrs}" if self.attrs else "")
        ]
        for c in self.children:
            lines.append(c.tree(indent + 1))
        return "\n".join(lines)

    def find(self, name: str) -> List["Span"]:
        """All descendants (including self) with the given name."""
        out = [self] if self.name == name else []
        for c in self.children:
            out.extend(c.find(name))
        return out


class _NullSpan:
    """No-op span handle for disabled telemetry — same surface as Span."""

    __slots__ = ()
    name = kind = ""
    attrs: Dict[str, Any] = {}
    duration_s = 0.0
    fenced = False

    def set(self, **attrs: Any) -> "_NullSpan":
        return self

    def fence(self, *values: Any) -> Any:
        # still fence: disabled telemetry must not change *numerics* or
        # memory pressure, but the overhead baseline should not silently
        # skip synchronization the instrumented path performs
        import jax

        out = tuple(jax.block_until_ready(v) for v in values)
        return out[0] if len(out) == 1 else out


_NULL = _NullSpan()


class Tracer:
    """Stack-based span builder over one clock + metric registry.

    Completed *root* spans are kept in a bounded deque (``capacity``);
    every completed span additionally aggregates into the
    ``span_seconds{name=...}`` histogram so the per-stage breakdown is
    available without tree-walking.
    """

    def __init__(self, clock, registry=None, capacity: int = 256,
                 enabled: bool = True):
        self.clock = clock
        self.registry = registry
        self.capacity = capacity
        self.enabled = enabled
        self._stack: List[Span] = []
        self._roots: Deque[Span] = deque(maxlen=capacity)

    @contextlib.contextmanager
    def span(self, name: str, kind: str = "host",
             **attrs: Any) -> Iterator[Span]:
        if not self.enabled:
            yield _NULL
            return
        with TraceAnnotation(name):
            s = Span(name, kind, self.clock.now(), attrs)
            self._stack.append(s)
            try:
                yield s
            finally:
                s.t1 = self.clock.now()
                popped = self._stack.pop()
                assert popped is s, "span stack corrupted"
                if self._stack:
                    self._stack[-1].children.append(s)
                else:
                    self._roots.append(s)
                if self.registry is not None:
                    self.registry.histogram(
                        "span_seconds",
                        help="wall time per request-path stage",
                        unit="s",
                        labels=("name", "kind"),
                    ).observe(s.duration_s, name=s.name, kind=s.kind)

    def roots(self) -> List[Span]:
        """Completed top-level spans, oldest first (bounded window)."""
        return list(self._roots)

    def last_root(self, name: Optional[str] = None) -> Optional[Span]:
        for s in reversed(self._roots):
            if name is None or s.name == name:
                return s
        return None

    def clear(self) -> None:
        self._roots.clear()
