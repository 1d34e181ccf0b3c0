"""ObsConfig: the observability switchboard carried by ``ExecConfig``.

A frozen, hashable dataclass — it rides inside ``api.ExecConfig`` (a
leaf-free pytree whose every field is static jit metadata), so it must
compare/hash by value and never hold mutable state. The mutable side of
observability (the span list, the ledger entries) lives in
``obs.report.ObsSession``, which a ``Workspace`` constructs FROM this
config; the config only says what to collect.

``enabled=False`` (the default) is the near-zero-overhead contract: a
Workspace built with it never constructs a session — every ``span()``
call resolves to a bare profiler annotation (``obs.trace.ProfilerSpan``:
no tracer state) and every ledger charge is a no-op method on
``obs.trace.NULL_OBS``. The annotations and the recompile sentinel
(``obs.compile``) are the always-on pieces: an annotation costs well
under a microsecond with no profiler running, and the sentinel runs
only at jit-trace and compile time.

This module deliberately imports nothing from ``repro`` (and nothing
heavier than ``dataclasses``) so ``api.config`` can import it without
cycles or import-time cost.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """What the observability layer collects for one session.

    Fields
    ------
    enabled:
        Master switch. ``False`` (default): no session is created, every
        span is a bare profiler annotation and every charge a no-op.
    spans:
        Collect the nested wall-time span tree (``obs.trace.Tracer``).
        Spans reach a ``jax.profiler`` capture either way.
    ledger:
        Charge the analytic traffic ledger (``obs.ledger.Ledger``) at the
        instrumented call sites — hoist builds, permutation batches, the
        distance production sweep.
    probe:
        Measure the session's jitted entry points at report time
        (``obs.probe``: AOT-compiled flop/byte/peak counts) and
        reconcile them against the analytic models (``obs.drift``) into
        the report's ``measured`` and ``drift`` sections. Probing is
        compile-time-only work at report() — nothing on the execution
        hot path — but it does cost a few ahead-of-time compiles per
        session geometry, so it follows the master switch.
    """

    enabled: bool = False
    spans: bool = True
    ledger: bool = True
    probe: bool = True

    def __post_init__(self):
        for f in ("enabled", "spans", "ledger", "probe"):
            v = getattr(self, f)
            if not isinstance(v, bool):
                raise ValueError(f"ObsConfig.{f} must be a bool, "
                                 f"got {v!r}")
