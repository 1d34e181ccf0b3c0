"""ExecConfig: one home for every execution knob in the analysis stack.

Before the `repro.api` redesign the knobs that decide *how* an analysis
executes — which centering implementation, whether to materialize the
Gower matrix, tile sizes, the permutation batch, the device mesh — were
scattered as inconsistent per-function kwargs (`pcoa(block=...)`,
`permutation_test(batch_size=...)`, ...). ``ExecConfig`` collects them in
a single frozen pytree dataclass that threads uniformly through
``api.Workspace``, ``core.pcoa``, ``core.mantel`` and ``stats.engine``.

It is registered as a *leaf-free* pytree (every field is static metadata),
so it can sit inside jitted pytrees or static args: two configs compare
equal iff every knob matches, and each distinct config keys its own jit
cache entry.

This module deliberately imports nothing from ``repro`` except
``repro.obs.config`` (itself import-free) so any layer — core, stats,
kernels — can import it without cycles.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Union

import jax

from repro.obs.config import ObsConfig


# mirror of repro.dist.METRICS — kept literal here because this module
# imports nothing from repro (pinned in sync by tests/test_dist.py)
_KNOWN_METRICS = ("braycurtis", "canberra", "cityblock", "euclidean",
                  "jaccard", "unweighted_unifrac")


@partial(jax.tree_util.register_dataclass,
         data_fields=[],
         meta_fields=["centering_impl", "materialize", "block",
                      "batch_size", "mesh", "device", "metric",
                      "feature_block", "chunk", "auto", "tune_profile",
                      "obs"])
@dataclasses.dataclass(frozen=True)
class ExecConfig:
    """Execution configuration shared by every analysis entry point.

    Fields
    ------
    centering_impl:
        Implementation used whenever a *materialized* Gower-centered matrix
        is required (PERMANOVA's hoist, ``pcoa(method="eigh")``, the
        ``materialize=True`` fallback) — ``"ref"`` (eager multi-pass
        oracle), ``"fused"`` (single-jit two-pass, the default) or
        ``"distributed"`` (shard_map over ``mesh``).
    materialize:
        ``True`` restores the legacy materialize-then-solve ordination path
        (the benchmark baseline); ``False`` (default) runs PCoA matrix-free
        through the operator.
    block:
        Row tile size of distance production and of the operator matvec.
        ``"auto"``: solved by ``repro.tune`` as the largest
        lane-snapped block whose modeled resident set (one D tile + the
        x panels; plus the production strip when feature-backed) fits
        the backend budget, *capped at the default* (shrink-only, like
        feature_block): distance production is bitwise-invariant in
        block (each produced element reduces the full feature axis
        regardless of row-panel membership), but the operator matvec
        re-associates panel partial sums, so auto keeps the default
        geometry whenever it fits — bitwise-identical results — and
        shrinks only under budget pressure, where matvec-backed
        ordination/PERMANOVA agree to fp tolerance instead.
    batch_size:
        Permutations evaluated per engine tile — for the batch-fused
        statistics (Mantel family, ANOSIM) this is exactly the B
        dimension of ``kernels.permute_reduce``: each hoisted condensed
        invariant streams ONCE per tile and is reused by all B
        permutations, so bigger batches mean less traffic per
        permutation (peak memory is one (B, chunk) gather tile). ``None``
        (default) keeps each test's tuned default (32 everywhere since
        the condensed loop; the engine pads partial tiles so any K
        compiles exactly one program). ``"auto"``: solved from
        (n, budget) only — NEVER from K, so the one padded per-batch
        program keeps serving every K — as the largest batch whose
        (B, chunk) gather tile + (B, n) order block stay budget-resident
        (capped at 128, where the 3m/B amortization is within 3% of its
        asymptote); batch choice is bitwise-neutral (pinned by the
        engine's batch-size-invariance test).
    mesh:
        Optional ``jax.sharding.Mesh`` for the distributed paths
        (``centering_impl="distributed"``, distributed matvec/engine).
    device:
        Optional ``jax.Device`` the Workspace pins its canonical matrix to
        (``None``: wherever jax placed it).
    metric:
        Default beta-diversity metric for feature-table sessions
        (``Workspace.from_features`` with ``metric=None``) — any
        ``repro.dist`` registry name ("braycurtis", "euclidean",
        "jaccard", "canberra", "cityblock", "unweighted_unifrac"; the
        last needs the table's tree, ``tree=``).
    feature_block:
        Feature-axis chunk of the pairwise metric reduce: bounds the
        per-tile broadcast term at (rows, cols, feature_block). A
        product metric (unweighted UniFrac) ignores it: each panel is
        one matrix product over the whole width
        (``resolved_tiles()["feature_block_executed"]`` says so).
        ``"auto"``: the solver only ever *shrinks* this under budget
        pressure, never grows it — feature_block is value-affecting
        (the metric accumulators merge once per feature chunk and fp
        addition is non-associative), and shrink-only keeps the default
        geometry whenever it fits, so auto stays bitwise-identical to
        the default on any problem the default could run.
    chunk:
        Condensed-stream chunk of ``kernels.permute_reduce`` (floats per
        scan step). ``None`` (default) keeps the kernel's 64k constant;
        ``"auto"``: the largest chunk that keeps the (B, chunk) gather
        tile + (S, chunk) invariant tile budget-resident. The observed
        statistic is chunk-independent (the per-permutation path never
        chunks); null draws accumulate per chunk, so a different chunk
        can move a null sum by an ulp — with the engine's fixed PRNG
        key the draws, and hence the p-values, are deterministic per
        chunk choice.
    auto:
        ``True`` turns every knob still at its default into ``"auto"``
        semantics in one stroke: block, feature_block, batch_size and
        chunk are all solved by ``repro.tune.solve_tiles`` when the
        config is resolved against admitted data (``Workspace`` does
        this on construction; ``repro.serve`` admission resolves it the
        same way when a study is uploaded, so every pooled session
        serves tuned tiles; standalone callers use ``resolve(n, d)``).
        Knobs set to explicit concrete values are honored untouched.
    tune_profile:
        Optional path of a ``repro.tune.save_profile`` JSON (a
        calibrated ``BackendBudget``); when set, auto-solving fits
        against the persisted budget instead of the static defaults.
    obs:
        Observability switchboard (``repro.obs.ObsConfig``). The default
        (``enabled=False``) is the zero-overhead contract: no session is
        created, every span/charge resolves to the shared no-op
        singletons. ``ObsConfig(enabled=True)`` makes the Workspace own
        an ``ObsSession`` — span tracer + analytic traffic ledger +
        recompile-sentinel window — readable via ``Workspace.report()``.
        ``None`` coerces to the disabled default.
    """

    centering_impl: str = "fused"
    materialize: bool = False
    block: Union[int, str] = 256
    batch_size: Union[int, str, None] = None
    mesh: Optional[Any] = None
    device: Optional[Any] = None
    metric: str = "braycurtis"
    feature_block: Union[int, str] = 128
    chunk: Union[int, str, None] = None
    auto: bool = False
    tune_profile: Optional[str] = None
    obs: Optional[ObsConfig] = ObsConfig()

    def __post_init__(self):
        if self.obs is None:
            object.__setattr__(self, "obs", ObsConfig())
        if not isinstance(self.obs, ObsConfig):
            raise ValueError(f"obs must be an ObsConfig (or None), "
                             f"got {self.obs!r}")
        if self.centering_impl not in ("ref", "fused", "distributed"):
            raise ValueError(f"unknown centering_impl "
                             f"{self.centering_impl!r}")
        if self.centering_impl == "distributed" and self.mesh is None:
            raise ValueError("centering_impl='distributed' requires a mesh")
        for knob in ("block", "feature_block"):
            v = getattr(self, knob)
            if not (v == "auto" or (isinstance(v, int) and v >= 1)):
                raise ValueError(f"{knob} must be an int >= 1 or 'auto', "
                                 f"got {v!r}")
        for knob in ("batch_size", "chunk"):
            v = getattr(self, knob)
            if not (v is None or v == "auto"
                    or (isinstance(v, int) and v >= 1)):
                raise ValueError(f"{knob} must be an int >= 1, 'auto' or "
                                 f"None, got {v!r}")
        if self.metric not in _KNOWN_METRICS:
            raise ValueError(f"unknown metric {self.metric!r}; "
                             f"available: {list(_KNOWN_METRICS)}")

    def replace(self, **changes) -> "ExecConfig":
        """A copy with ``changes`` applied (``dataclasses.replace``)."""
        return dataclasses.replace(self, **changes)

    def resolve_batch_size(self, explicit: Optional[int],
                           default: int) -> int:
        """Precedence: explicit call-site arg > config > per-test
        default. An unresolved ``"auto"`` falls through to the engine,
        which solves it against the statistic's n."""
        if explicit is not None:
            return explicit
        if self.batch_size is not None:
            return self.batch_size
        return default

    @property
    def needs_resolution(self) -> bool:
        """True when some knob still carries auto semantics — i.e.
        ``resolve()`` would change this config."""
        return bool(self.auto or "auto" in (self.block, self.feature_block,
                                            self.batch_size, self.chunk))

    def resolve(self, n: int, d: Optional[int] = None
                ) -> "tuple[ExecConfig, Optional[Any]]":
        """Materialize auto knobs against a concrete problem size.

        Returns ``(resolved_config, tuned)`` — ``tuned`` is the
        ``repro.tune.TunedTiles`` record (chosen tiles + modeled bytes
        + the budget they were fit against) or ``None`` when nothing
        asked for tuning. ``Workspace`` calls this at admission;
        standalone users can call it directly. The import is lazy so
        this module keeps its no-repro-imports contract for every
        config that never opts in.
        """
        if not self.needs_resolution:
            return self, None
        from repro.tune.solve import resolve_exec_config
        return resolve_exec_config(self, n, d)
