"""Span tracer: nested wall-time spans with phase tags and cost attrs.

The paper's discipline is "account for every pass over the data"; the
ROADMAP's corollary is that container wall-clock is ±40% noise, so the
*structure* of a run — which phase ran, how often, against which backend,
with which analytic cost — is the trustworthy signal and the timing is
the informational overlay. A ``Span`` records both: host-side wall time
(``perf_counter``; note jax dispatch is async, so a span bounds the
host's dispatch+sync work, not device occupancy — the profiler
annotation below lines spans up with the device ops) plus a phase tag
from the analysis stack's vocabulary:

* ``hoist``      — a permutation-invariant O(n²)/O(m) artifact build
  (the HoistCache miss path);
* ``per_perm``   — a Monte-Carlo permutation loop (the stats engine);
* ``production`` — the tiled feature-table → condensed-distance sweep
  (``repro.dist``);
* ``solve``      — an eigensolve / subspace iteration (``core.pcoa``);
* ``step``       — a training/serving step (``runtime.monitor``);
* ``serve``      — front-door work in ``repro.serve`` (admission, tile
  scheduling, request lifecycle).

Spans nest (a ``ws.permanova`` span contains its ``hoist:gram`` child
and the engine's ``per_perm`` span) and export as plain dicts / JSON and
as Chrome ``trace_event`` JSON (load in ``chrome://tracing`` /
Perfetto).

Every span, with or without a session, is also a
``jax.profiler.TraceAnnotation`` of its name: any ``jax.profiler``
capture holds the program's spans on the host plane, on the profiler's
clock, beside the device ops. With no profiler running an annotation
costs well under a microsecond.

The session-less path is the contract that lets every hot call site
stay instrumented unconditionally: with no active session,
``current_obs()`` returns the shared ``NULL_OBS`` singleton whose
``span()`` returns a bare ``ProfilerSpan`` — the annotation alone, with
no tracer state and no ledger entry. ``tests/test_obs.py`` pins that
contract and a generous per-call time bound.

This module imports nothing from ``repro`` (jax only, for the profiler
annotation) so any layer can import it without cycles.
"""

from __future__ import annotations

import json
import time
from typing import Optional

from jax.profiler import TraceAnnotation

#: the phase vocabulary — see the module docstring
PHASES = ("hoist", "per_perm", "production", "solve", "step", "serve")


class Span:
    """One timed, attributed, nestable region.

    Use as a context manager (``with tracer.span(...)``) or drive
    ``begin()``/``end()`` explicitly (the ``StepMonitor`` style). Attrs
    are free-form key→value pairs: impl/backend tags, analytic cost
    terms, shapes. ``add()`` attaches more after creation (e.g. a result
    computed inside the span).
    """

    __slots__ = ("name", "phase", "attrs", "t0", "duration", "children",
                 "_tracer", "_session", "_ann")

    def __init__(self, tracer: "Tracer", name: str,
                 phase: Optional[str] = None, session=None, **attrs):
        if phase is not None and phase not in PHASES:
            raise ValueError(f"unknown span phase {phase!r}; "
                             f"expected one of {PHASES} or None")
        self.name = name
        self.phase = phase
        self.attrs = attrs
        self.t0: Optional[float] = None
        self.duration: Optional[float] = None
        self.children: list = []
        self._tracer = tracer
        self._session = session
        self._ann = None

    # -- lifecycle ---------------------------------------------------------
    def begin(self) -> "Span":
        self.t0 = time.perf_counter()
        self._tracer._open(self)
        if self._session is not None:
            push_obs(self._session)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        return self

    def end(self) -> "Span":
        if self.t0 is None:
            raise RuntimeError(f"span {self.name!r} ended before begin()")
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._session is not None:
            pop_obs(self._session)
        self.duration = time.perf_counter() - self.t0
        self._tracer._close(self)
        return self

    def __enter__(self) -> "Span":
        return self.begin()

    def __exit__(self, *exc) -> bool:
        self.end()
        return False

    def add(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    # -- export ------------------------------------------------------------
    def to_dict(self) -> dict:
        d = {"name": self.name, "phase": self.phase,
             "duration_s": self.duration, "attrs": dict(self.attrs)}
        if self.children:
            d["children"] = [c.to_dict() for c in self.children]
        return d

    def __repr__(self):
        dur = f"{self.duration:.4f}s" if self.duration is not None else "open"
        return f"Span({self.name!r}, phase={self.phase!r}, {dur})"


class Tracer:
    """Owns one run's span tree.

    ``spans`` holds the completed root spans in completion order;
    nesting is by begin/end bracketing (a span begun while another is
    open becomes its child). Not thread-safe — one tracer per session,
    like the HoistCache it instruments.
    """

    def __init__(self):
        self.epoch = time.perf_counter()
        self.spans: list[Span] = []
        self._stack: list[Span] = []

    def span(self, name: str, phase: Optional[str] = None, session=None,
             **attrs) -> Span:
        """A new (unstarted) span — enter it (``with``) or ``begin()``."""
        return Span(self, name, phase, session=session, **attrs)

    def record(self, name: str, seconds: float,
               phase: Optional[str] = None, **attrs) -> Span:
        """Append a pre-timed span (no live begin/end window) — the
        ``StepMonitor.record`` path, where the caller measured the
        duration itself."""
        s = Span(self, name, phase, **attrs)
        s.t0 = time.perf_counter() - seconds
        s.duration = seconds
        self._close(s)
        return s

    # -- span plumbing -----------------------------------------------------
    def _open(self, span: Span) -> None:
        self._stack.append(span)

    def _close(self, span: Span) -> None:
        if self._stack and self._stack[-1] is span:
            self._stack.pop()
        parent = self._stack[-1] if self._stack else None
        if parent is not None:
            parent.children.append(span)
        else:
            self.spans.append(span)

    # -- queries -----------------------------------------------------------
    def _walk(self, spans=None):
        for s in (self.spans if spans is None else spans):
            yield s
            yield from self._walk(s.children)

    def count(self, phase: Optional[str] = None) -> int:
        return sum(1 for s in self._walk()
                   if phase is None or s.phase == phase)

    def total(self, phase: str) -> float:
        """Summed wall seconds of every span tagged ``phase`` (children
        of a same-phase parent still count — phases don't self-nest in
        the instrumented stack)."""
        return sum(s.duration or 0.0 for s in self._walk()
                   if s.phase == phase)

    # -- export ------------------------------------------------------------
    def to_dicts(self) -> list:
        return [s.to_dict() for s in self.spans]

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.to_dicts(), indent=indent, default=str)

    def to_chrome_trace(self) -> list:
        """Chrome/Perfetto ``trace_event`` list (``ph="X"`` complete
        events, µs timebase) — dump with ``json.dump`` and load in
        ``chrome://tracing`` or https://ui.perfetto.dev."""
        events = []

        def emit(span: Span):
            if span.t0 is None or span.duration is None:
                return
            events.append({
                "name": span.name, "ph": "X", "pid": 0, "tid": 0,
                "cat": span.phase or "span",
                "ts": (span.t0 - self.epoch) * 1e6,
                "dur": span.duration * 1e6,
                "args": {k: str(v) for k, v in span.attrs.items()},
            })
            for c in span.children:
                emit(c)

        for s in self.spans:
            emit(s)
        return events

    def tree_lines(self, min_seconds: float = 0.0) -> list:
        """The span tree as indented text lines (the example's session
        epilogue printer)."""
        lines = []

        def walk(span: Span, depth: int):
            if span.duration is not None and span.duration < min_seconds:
                return
            dur = (f"{span.duration * 1e3:9.2f} ms"
                   if span.duration is not None else "     open")
            tag = f" [{span.phase}]" if span.phase else ""
            attrs = ", ".join(f"{k}={v}" for k, v in span.attrs.items()
                              if k in ("impl", "backend", "kernel", "method",
                                       "n", "permutations", "batch_size"))
            lines.append(f"{dur}  {'  ' * depth}{span.name}{tag}"
                         f"{'  (' + attrs + ')' if attrs else ''}")
            for c in span.children:
                walk(c, depth + 1)

        for s in self.spans:
            walk(s, 0)
        return lines


# --------------------------------------------------------------------------
# The session-less path + the ambient session stack
# --------------------------------------------------------------------------
class ProfilerSpan(TraceAnnotation):
    """A span with no session: the profiler annotation of its name and
    nothing else — no tracer state, no ledger entry. Same surface as
    ``Span``; ``add`` drops its attrs."""

    __slots__ = ()

    def begin(self) -> "ProfilerSpan":
        self.__enter__()
        return self

    def end(self) -> "ProfilerSpan":
        self.__exit__(None, None, None)
        return self

    def add(self, **attrs) -> "ProfilerSpan":
        return self


class _NullObs:
    """THE session-less session: every instrumented call site talks to
    this when observability is off (or no session is ambient). Same
    method surface as ``obs.report.ObsSession``: spans are bare profiler
    annotations, charges are free no-ops."""

    __slots__ = ()
    enabled = False

    def span(self, name, phase=None, **attrs):
        return ProfilerSpan(name)

    def charge(self, op, floats, **params):
        return None

    def charge_hoist(self, artifact, n, table=None):
        return None

    def charge_perm_batch(self, op, n, permutations, batch, **params):
        return None

    def charge_production(self, n, d, block, **params):
        return None


NULL_OBS = _NullObs()

# the ambient stack: a Workspace-level span pushes its session so free
# functions deeper in the stack (stats.engine, core.pcoa, dist.driver)
# attach their spans/charges to the session that invoked them. Plain
# list, not a contextvar: the analysis stack is synchronous.
_STACK: list = []


def current_obs():
    """The innermost active session, or ``NULL_OBS`` (the free path)."""
    return _STACK[-1] if _STACK else NULL_OBS


def push_obs(session) -> None:
    _STACK.append(session)


def pop_obs(session) -> None:
    if _STACK and _STACK[-1] is session:
        _STACK.pop()
    elif session in _STACK:              # unbalanced exit: drop it anyway
        _STACK.remove(session)
