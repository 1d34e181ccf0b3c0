"""ObsSession + RunReport: one object per run, one JSON per run.

``ObsSession`` is the mutable counterpart of ``ObsConfig``: a tracer, a
ledger, and a baseline snapshot of the process-global recompile
sentinel, owned by a ``Workspace`` (or any driver) for one run. Its
``span()`` pushes the session onto the ambient stack
(``obs.trace.current_obs``), which is how the free functions deeper in
the call chain — ``stats.engine``, ``core.pcoa``, ``dist.driver`` —
attach their spans and ledger charges to the session that invoked them
without threading an argument through every signature.

``RunReport`` is the assembled artifact: span tree, ledger totals,
HoistCache hit/miss snapshot, and sentinel deltas, as one JSON document.
``benchmarks/run.py --smoke`` writes one per CI run (uploaded as a
workflow artifact) and gates on its ``compile`` section; the README's
Observability section shows a worked example.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Optional

from repro.obs.compile import sentinel
from repro.obs.config import ObsConfig
from repro.obs.ledger import Ledger
from repro.obs.trace import ProfilerSpan, Tracer


class ObsSession:
    """One run's live observability state (see module docstring)."""

    enabled = True

    def __init__(self, config: Optional[ObsConfig] = None):
        self.config = config if config is not None else ObsConfig(
            enabled=True)
        self.tracer = Tracer()
        self.ledger = Ledger()
        self.sentinel = sentinel
        self.sentinel_base = sentinel.snapshot()
        self.prep_base = sentinel.prep()

    # -- spans -------------------------------------------------------------
    def span(self, name: str, phase: Optional[str] = None, **attrs):
        """A session span: entering it also makes this session ambient
        (``current_obs()``) for the enclosed call chain. With
        ``config.spans`` off it is the bare profiler annotation."""
        if not self.config.spans:
            return ProfilerSpan(name)
        return self.tracer.span(name, phase, session=self, **attrs)

    # -- ledger charges (gated on config.ledger) ---------------------------
    def charge(self, op, floats, **params):
        if self.config.ledger:
            return self.ledger.charge(op, floats, **params)

    def charge_hoist(self, artifact, n, table=None):
        if self.config.ledger:
            return self.ledger.charge_hoist(artifact, n, table=table)

    def charge_perm_batch(self, op, n, permutations, batch, **params):
        if self.config.ledger:
            return self.ledger.charge_perm_batch(op, n, permutations,
                                                 batch, **params)

    def charge_production(self, n, d, block, **params):
        if self.config.ledger:
            return self.ledger.charge_production(n, d, block, **params)

    # -- sentinel ----------------------------------------------------------
    def compile_delta(self) -> dict:
        """Traces/programs noted since this session began."""
        return self.sentinel.since(self.sentinel_base)

    def prep_delta(self) -> dict:
        """Program preparation counted since this session began."""
        return self.sentinel.prep_since(self.prep_base)


@dataclasses.dataclass
class RunReport:
    """One run, one document: spans + ledger + cache + compile counts
    + program preparation.

    ``meta`` carries provenance (jax version, backend, session shape);
    ``spans`` is the tracer's nested dict tree; ``ledger`` the totals
    plus every entry; ``cache`` the HoistCache hit/miss counters and
    generation; ``compile`` the sentinel's per-entry-point trace and
    program counts for the run's window; ``prep`` the program
    preparation counted in it (``sentinel.prep()``: ``seconds``, per
    kind counts and seconds, cache hits and misses, by entry point).
    """

    meta: dict
    spans: list
    ledger: dict
    cache: dict
    compile: dict
    measured: dict = dataclasses.field(default_factory=dict)
    drift: dict = dataclasses.field(default_factory=dict)
    prep: dict = dataclasses.field(default_factory=dict)

    def to_dict(self) -> dict:
        return {"meta": self.meta, "spans": self.spans,
                "ledger": self.ledger, "cache": self.cache,
                "compile": self.compile, "measured": self.measured,
                "drift": self.drift, "prep": self.prep}

    def to_json(self, indent: Optional[int] = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent, default=str)

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            f.write(self.to_json())
        return path

    # convenience accessors for the gated quantities
    @property
    def hoist_passes(self) -> float:
        return self.ledger.get("hoist_passes", 0.0)

    @property
    def total_bytes(self) -> float:
        return self.ledger.get("total_bytes", 0.0)

    def programs(self, name: str) -> int:
        return self.compile.get(name, {}).get("programs", 0)

    @property
    def drift_ok(self) -> bool:
        """True when the drift section is absent OR every reconciled
        verdict landed inside its tolerance band."""
        return bool(self.drift.get("within_tolerance", True))


def _cache_section(cache) -> dict:
    """A HoistCache, stringified for JSON (tuple keys become strings)."""
    if cache is None:
        return {}
    return {
        "hits": {str(k): v for k, v in cache.hits.items()},
        "misses": {str(k): v for k, v in cache.misses.items()},
        "keys": sorted(str(k) for k in cache.keys()),
    }


def build_report(session: Optional[ObsSession] = None, cache=None,
                 meta: Optional[dict] = None,
                 measured: Optional[dict] = None,
                 drift: Optional[dict] = None) -> RunReport:
    """Assemble a ``RunReport`` from a session (tracer + ledger +
    sentinel window) and an optional HoistCache. With ``session=None``
    (observability disabled) the report still carries the cache
    counters and the sentinel's full process snapshot and prep counters
    — the always-on telemetry — with empty spans and ledger.

    ``measured`` is a ``{name: ProbeRecord}`` mapping from
    ``obs.probe.probe_session`` (serialized here); ``drift`` the
    already-built ``DriftSentinel.reconcile`` section."""
    import jax

    base_meta = {"jax": jax.__version__, "backend": jax.default_backend()}
    if meta:
        base_meta.update(meta)
    measured_section = {name: (rec.to_dict() if hasattr(rec, "to_dict")
                               else dict(rec))
                        for name, rec in (measured or {}).items()}
    if session is not None:
        return RunReport(meta=base_meta,
                         spans=session.tracer.to_dicts(),
                         ledger=session.ledger.to_dict(),
                         cache=_cache_section(cache),
                         compile=session.compile_delta(),
                         measured=measured_section,
                         drift=dict(drift or {}),
                         prep=session.prep_delta())
    return RunReport(meta=base_meta, spans=[], ledger={},
                     cache=_cache_section(cache),
                     compile=sentinel.snapshot(),
                     measured=measured_section,
                     drift=dict(drift or {}),
                     prep=sentinel.prep())
