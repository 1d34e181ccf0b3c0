"""repro.obs — always-on observability for the analysis stack.

Seven pieces, one discipline ("profile first, then trust the model" —
the SSD-profiling study's rule, applied to our own runtime):

* ``obs.trace``   — nested span tracer with phase tags
  (hoist | per_perm | production | solve | step), JSON + Chrome
  ``trace_event`` export; every span, with or without a session, is a
  ``jax.profiler.TraceAnnotation`` too, so any profiler capture holds
  the program's spans;
* ``obs.ledger``  — THE audited analytic-traffic registry (hoist pass
  tables, Mantel per-permutation models, production feature reads),
  shared by the benchmarks and charged live by the instrumented stack;
* ``obs.compile`` — the recompile sentinel: jit trace/program counts
  per wrapped entry point, with a runtime guard for the "one trace
  serves any K" invariant; the program-preparation counter (trace,
  lowering, compile and cache-load seconds); and ``hlo_texts``, the
  compiled HLO whose metadata holds each instruction's
  ``jax.named_scope`` path;
* ``obs.report``  — ``ObsSession`` (one run's tracer+ledger+sentinel
  window) and ``RunReport`` (the one-JSON-per-run artifact CI uploads);
* ``obs.probe``   — the MEASURED half: AOT-compiled flop/byte/peak
  counts per jitted entry point (``cost_analysis`` + ``memory_analysis``
  + scan-corrected HLO byte counting, absorbed from the retired
  ``repro.roofline``);
* ``obs.drift``   — the ``DriftSentinel`` reconciling measured probes
  against the ledger/tune models with per-backend tolerance bands;
* ``obs.metrics`` — allocation-light ``Counter``/``Gauge``/``Histogram``
  primitives (JSON + Prometheus text export) behind the serve latency
  percentiles and the step monitor.

Enable per session via ``ExecConfig(obs=ObsConfig(enabled=True))``;
read the result with ``Workspace.report()``.
"""

from repro.obs.compile import (CompileSentinel, RecompileError, note_trace,
                               sentinel)
from repro.obs.config import ObsConfig
from repro.obs.drift import DriftSentinel, DriftVerdict
from repro.obs.ledger import (FEATURE_HOIST_PASSES, HOIST_PASSES, Ledger,
                              LedgerEntry, hoist_floats, perm_traffic_floats,
                              production_floats)
from repro.obs.metrics import (DEFAULT_LATENCY_BUCKETS, NULL_HISTOGRAM,
                               Counter, Gauge, Histogram, prometheus_text)
from repro.obs.probe import (ProbeRecord, probe_lowered, probe_session,
                             probe_table, scan_corrected_bytes)
from repro.obs.report import ObsSession, RunReport, build_report
from repro.obs.trace import (NULL_OBS, PHASES, ProfilerSpan, Span, Tracer,
                             current_obs)

__all__ = [
    "CompileSentinel", "RecompileError", "note_trace", "sentinel",
    "ObsConfig",
    "DriftSentinel", "DriftVerdict",
    "FEATURE_HOIST_PASSES", "HOIST_PASSES", "Ledger", "LedgerEntry",
    "hoist_floats", "perm_traffic_floats", "production_floats",
    "DEFAULT_LATENCY_BUCKETS", "NULL_HISTOGRAM", "Counter", "Gauge",
    "Histogram", "prometheus_text",
    "ProbeRecord", "probe_lowered", "probe_session", "probe_table",
    "scan_corrected_bytes",
    "ObsSession", "RunReport", "build_report",
    "NULL_OBS", "PHASES", "ProfilerSpan", "Span", "Tracer", "current_obs",
]
