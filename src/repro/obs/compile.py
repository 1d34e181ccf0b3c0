"""Recompile sentinel: trace counts, program preparation time and the
compiled HLO of each jitted entry point, live.

The engine-hardening invariant of the padded ``per_batch`` path is "ONE
trace serves any K" — the orders are padded to full batch tiles so the
canonical 999-permutation run never traces a second trailing-block
program. Until now that was only a test-time property (a Python-side
counter inside a probe statistic); this module makes it an always-on
runtime counter with an assertable guard, so CI's smoke pass — and any
production session — fails loudly the day a shape leaks back into a
trace signature.

Mechanism: a jitted function's **Python body runs only at trace time**,
so a ``note_trace(name, signature)`` call placed inside the body is a
zero-cost-per-call trace counter (verified for nested jits too: an
inner jit's body runs once per distinct signature even across outer
retraces — jax caches the inner jaxpr by abstract values). Each note
records:

* ``traces``   — body executions: how many times jax traced this entry;
* ``programs`` — distinct signatures: how many separate compiled
  executables exist. A genuine recompile regression (e.g. the old
  trailing-block special case) shows up as a NEW signature; a
  legitimately different workload (another n, another batch size) does
  too — which is exactly what the signature tuple is for: the guard
  scopes to a window where the workload parameters that SHOULD be
  shape-stable actually are.

A note that also passes the jitted function, its arguments and its
static arguments keeps them as ``ShapeDtypeStruct``s, so the program can
be lowered again later without any array: ``compiled(module)`` does
that and returns each signature's compiled HLO text (once: the texts
are kept), whose ``op_name`` metadata holds each
instruction's ``jax.named_scope`` path. A profiler trace names device
ops by instruction, so a reader can put each op of a trace under the
scope that issued it. It runs only when asked, never on the hot path.
The call site of such an entry point counts its executions,
``note_run(fn, signature)``, by the same signature, so a reader can
weigh each program's device time by how often it ran (``runs``).

The sentinel also listens to ``jax.monitoring`` (registered once, at
import) and keeps **program preparation** counts and seconds: jaxpr
tracing, lowering to MLIR, backend compiles (which include persistent
cache loads) and cache loads, with the cache's hits and misses, by entry
point where the event names one. ``prep_seconds()`` is the wall time the
process spent preparing programs: the union of those events' intervals,
so a trace nested inside another is not counted twice. ``prep()`` and
``prep_since()`` give the figures; a session's ``RunReport.prep`` holds
its window's.

The sentinel is process-global because the jit caches it mirrors are
process-global; scope assertions with ``snapshot()``/``since()`` or the
``expect()`` context manager.
"""

from __future__ import annotations

import bisect
import contextlib
import re
import threading
from collections import Counter
from typing import Optional

import jax

#: the ``jax.monitoring`` durations the prep counter keeps, by kind.
#: ``compile`` encloses ``cache_load``: a persistent-cache hit is timed
#: inside the backend compile event that it replaces.
PREP_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
#: the events whose intervals make up ``prep_seconds``
_WALL_EVENTS = frozenset(e for e, k in PREP_EVENTS.items()
                         if k != "cache_load")
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


class RecompileError(RuntimeError):
    """An entry point traced more distinct programs than its budget."""


def _module_name(fun_name: str) -> str:
    """The profiler's module name for a monitoring event's ``fun_name``:
    ``jit(f)`` (lowering, compile) and ``f`` (tracing) are both
    ``jit_f``."""
    m = re.fullmatch(r"(\w+)\((.*)\)", fun_name)
    return f"{m[1]}_{m[2]}" if m else f"jit_{fun_name}"


#: a compiler option at its default: it keys a compile apart from the
#: executables jax keeps, and changes nothing in what is compiled
_FRESH = {"xla_embed_ir_in_executable": False}


def _abstract(tree):
    """``tree`` with every array leaf as a ``ShapeDtypeStruct``."""
    def leaf(x):
        aval = jax.typeof(x)
        return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                    weak_type=aval.weak_type)
    return jax.tree_util.tree_map(leaf, tree)


def _diff(now, base):
    """``now - base`` over nested dicts of numbers, zeros left out."""
    if isinstance(now, dict):
        out = {}
        for k, v in now.items():
            d = _diff(v, base.get(k, {} if isinstance(v, dict) else 0))
            if d:
                out[k] = d
        return out
    return now - base


class CompileSentinel:
    """Per-entry-point trace and program counters, program preparation
    counters, and the signatures ``hlo_texts`` lowers again."""

    def __init__(self):
        self._traces: Counter = Counter()
        self._signatures: dict = {}          # name -> set of signatures
        self._replays: dict = {}             # module -> {signature: call}
        self._texts: dict = {}      # module -> {signature: (text, fresh)}
        self._runs: dict = {}                # module -> Counter(signature)
        self._entry_of: dict = {}            # module -> entry point name
        self._prep: dict = {}                # kind -> [count, seconds]
        self._prep_by: dict = {}             # entry -> kind -> [n, s]
        self._cache: Counter = Counter()
        self._wall: list = []                # merged [start, end] pairs
        self._quiet = False
        self._lock = threading.Lock()

    # -- recording ---------------------------------------------------------
    def note(self, name: str, signature=None, fn=None, args=(),
             static=None) -> None:
        """Record one trace of ``name`` (call from inside the jitted
        body — it only runs at trace time). ``signature`` is any
        hashable tuple of the shapes/statics that key the jit cache;
        ``None`` degrades to trace counting only. ``fn`` (the jitted
        function), ``args`` (its traced arguments) and ``static`` (its
        static keyword arguments) let ``hlo_texts`` lower the program
        again; the arguments are kept as shapes only."""
        if self._quiet:
            return
        self._traces[name] += 1
        if signature is not None:
            self._signatures.setdefault(name, set()).add(signature)
        if fn is not None:
            module = f"jit_{fn.__name__}"
            self._entry_of[module] = name
            self._replays.setdefault(module, {})[signature] = (
                fn, _abstract(tuple(args)), dict(static or {}))
            self._texts.get(module, {}).pop(signature, None)

    def note_run(self, fn, signature) -> None:
        """Count one execution of the jitted ``fn`` (call from the host,
        where it is dispatched) under the ``signature`` its trace note
        records."""
        module = f"jit_{fn.__name__}"
        with self._lock:
            self._runs.setdefault(module, Counter())[signature] += 1

    def listen(self) -> "CompileSentinel":
        """Register the prep counter's ``jax.monitoring`` listeners
        (once per sentinel: jax keeps every listener it is given)."""
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_time_span_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def _on_duration(self, event, duration, **kwargs):
        kind = PREP_EVENTS.get(event)
        if kind is None or self._quiet:
            return
        with self._lock:
            tot = self._prep.setdefault(kind, [0, 0.0])
            tot[0] += 1
            tot[1] += duration
            fun_name = kwargs.get("fun_name")
            if fun_name:
                module = _module_name(str(fun_name))
                by = self._prep_by.setdefault(
                    self._entry_of.get(module, module), {})
                one = by.setdefault(kind, [0, 0.0])
                one[0] += 1
                one[1] += duration

    def _on_span(self, event, start, end, **kwargs):
        if event not in _WALL_EVENTS or self._quiet:
            return
        with self._lock:
            # keep the intervals sorted and disjoint: merge the new one
            # with every neighbour it overlaps (an enclosing trace ends
            # after the traces nested in it, so mostly the tail)
            wall = self._wall
            lo = hi = bisect.bisect_right(wall, [start, end])
            while lo > 0 and wall[lo - 1][1] >= start:
                lo -= 1
            while hi < len(wall) and wall[hi][0] <= end:
                hi += 1
            if lo < hi:
                start = min(start, wall[lo][0])
                end = max(end, wall[hi - 1][1])
            wall[lo:hi] = [[start, end]]

    def _on_event(self, event, **kwargs):
        key = CACHE_EVENTS.get(event)
        if key is not None and not self._quiet:
            with self._lock:
                self._cache[key] += 1

    @contextlib.contextmanager
    def _quiet_window(self):
        """Nothing inside is noted or counted (``hlo_texts``'s own
        lowering and compile)."""
        was, self._quiet = self._quiet, True
        try:
            yield
        finally:
            self._quiet = was

    # -- queries -----------------------------------------------------------
    def traces(self, name: str) -> int:
        return self._traces[name]

    def programs(self, name: str) -> int:
        return len(self._signatures.get(name, ()))

    def names(self):
        return sorted(set(self._traces) | set(self._signatures))

    def prep_seconds(self) -> float:
        """Wall seconds this process spent tracing, lowering, compiling
        and loading programs from the cache (nested events once)."""
        with self._lock:
            return sum(e - s for s, e in self._wall)

    def prep(self) -> dict:
        """The prep counters: ``seconds`` (``prep_seconds``), per kind
        ``{"count", "seconds"}`` (a trace's seconds include the traces
        nested in it), the cache's hits and misses, and ``by_entry``."""
        def kinds(table):
            return {k: {"count": n, "seconds": s}
                    for k, (n, s) in table.items()}
        seconds = self.prep_seconds()
        with self._lock:
            return {"seconds": seconds, **kinds(self._prep), **self._cache,
                    "by_entry": {e: kinds(t)
                                 for e, t in self._prep_by.items()}}

    def prep_since(self, base: dict) -> dict:
        """Prep counter deltas vs an earlier ``prep()`` (counters that
        did not move are omitted)."""
        return _diff(self.prep(), base)

    def snapshot(self) -> dict:
        """{entry point: {"traces", "programs"}} — embed in a RunReport
        or diff later with ``since()``."""
        return {n: {"traces": self.traces(n), "programs": self.programs(n)}
                for n in self.names()}

    def since(self, snap: dict) -> dict:
        """Counter deltas vs an earlier ``snapshot()`` (entries with no
        new traces are omitted)."""
        out = {}
        for n in self.names():
            base = snap.get(n, {"traces": 0, "programs": 0})
            dt = self.traces(n) - base["traces"]
            dp = self.programs(n) - base["programs"]
            if dt or dp:
                out[n] = {"traces": dt, "programs": dp}
        return out

    def runs(self, module: str) -> dict:
        """{signature: executions counted so far} of ``module``, named as
        the profiler names it (with or without its ``(fingerprint)``)."""
        with self._lock:
            return dict(self._runs.get(module.split("(")[0], {}))

    # -- scopes ------------------------------------------------------------
    def compiled(self, module: str, scope: Optional[str] = None) -> dict:
        """{signature: compiled HLO text (``as_text()``)} of every
        signature of ``module`` traced so far, ``module`` as the profiler
        names it (``jit__null_distribution``, with or without its
        ``(fingerprint)``). Each instruction's ``op_name`` metadata holds
        its ``jax.named_scope`` path. Lowers a signature again, unnoted
        and uncounted, the first time it is asked for: call it off the
        hot path.

        jax keeps the executables it ran: where the call's arguments were
        not committed to a device (a ``Workspace`` without a ``device``),
        the signature lowers again to the executable that ran, with no
        compile; else it compiles, with the metadata in the persistent
        compile cache's key. That key leaves metadata out by default, so
        an executable that ran may have been loaded from an entry that
        other code wrote, whose program differs in its metadata alone,
        with that code's scopes. With ``scope``, a text that does not
        name it is compiled once more, apart from jax's own executables
        (``_FRESH``) and with the metadata in the key: this code's
        scopes."""
        module = module.split("(")[0]
        records = dict(self._replays.get(module, {}))
        texts = self._texts.setdefault(module, {})
        flag = "jax_compilation_cache_include_metadata_in_key"
        was = getattr(jax.config, flag)
        jax.config.update(flag, True)
        try:
            with self._quiet_window():
                for signature, (fn, args, static) in records.items():
                    text, fresh = texts.get(signature, (None, False))
                    if text is None:
                        text = fn.lower(*args, **static).compile().as_text()
                    if scope is not None and scope not in text and not fresh:
                        text, fresh = fn.lower(*args, **static).compile(
                            _FRESH).as_text(), True
                    texts[signature] = text, fresh
        finally:
            jax.config.update(flag, was)
        return {sig: texts[sig][0] for sig in records}

    def hlo_texts(self, module: str) -> list:
        """The texts of ``compiled(module)``, in the order the signatures
        were first traced."""
        return list(self.compiled(module).values())

    # -- guards ------------------------------------------------------------
    @contextlib.contextmanager
    def expect(self, name: str, max_programs: int = 1,
               max_traces: Optional[int] = None):
        """Assert at runtime that the enclosed block traces ``name`` at
        most ``max_programs`` distinct programs (the "one trace serves
        any K" invariant: run two different K values inside the window
        and the padded path must not add a second program)."""
        base = self.snapshot()
        yield self
        delta = self.since(base).get(name, {"traces": 0, "programs": 0})
        if delta["programs"] > max_programs:
            raise RecompileError(
                f"{name}: {delta['programs']} distinct programs traced "
                f"in this window (budget: {max_programs}) — a shape or "
                f"static argument is leaking into the trace signature")
        if max_traces is not None and delta["traces"] > max_traces:
            raise RecompileError(
                f"{name}: {delta['traces']} traces in this window "
                f"(budget: {max_traces})")


#: THE process-global sentinel — jit caches are process-global, so their
#: mirror is too. Sessions embed ``snapshot()`` deltas in their reports.
sentinel = CompileSentinel().listen()


def note_trace(name: str, signature=None, fn=None, args=(),
               static=None) -> None:
    """Module-level shorthand the instrumented jit bodies call."""
    sentinel.note(name, signature, fn, args, static)


def note_run(fn, signature) -> None:
    """Module-level shorthand the instrumented call sites use."""
    sentinel.note_run(fn, signature)
