"""Workspace: hoist-once analysis sessions over one distance matrix.

The paper optimizes each analysis in isolation — validate in one pass,
center in two, hoist the permutation-invariants out of the Monte-Carlo
loop. But a real study (Sfiligoi et al. 2021, "Enabling microbiome
research on personal devices") runs *several* analyses on the **same**
matrix back-to-back, and the free-function API made each one re-pay the
O(n²) reads: ``pcoa`` and ``permdisp`` each re-hoisted the operator means,
``permanova`` re-centered, ``anosim`` re-ranked, every ``mantel`` call
re-normalized both matrices.

``Workspace`` is the session object that finishes the argument:

* construction validates (fused single-pass) and canonicalizes the matrix
  **once** — fp32 storage, optional device placement — exactly like the
  paper's §4.3 validation caching, extended to every derived artifact;
* the shared hoists live behind a lazy ``HoistCache`` keyed by artifact —
  row/global means of E = −½D∘D (``operator``), the materialized Gower
  matrix (``gram``), the condensed distances (``condensed``), the
  condensed rank transform (``ranks``), condensed normalization moments
  (``moments``), and full PCoA solutions (``coords``) — each computed on
  first use and reused by every later analysis in the session;
* every analysis method threads the session's single ``ExecConfig``
  through ``core.pcoa``, ``stats.engine`` and the kernel dispatchers, and
  returns the unified ``OrdinationResult`` / ``PermutationTestResult``
  with the resolved RNG key recorded.

The legacy free functions (``core.pcoa.pcoa``, ``core.mantel.mantel``,
``stats.permanova`` …) are thin wrappers over a one-shot Workspace — same
signatures, identical p-values per key — so the only thing a session
changes is how often D is read.

``Workspace.from_features`` extends the session one step upstream: the
distance matrix itself is produced by the tiled ``repro.dist`` driver in
CONDENSED layout, with the operator means accumulated during the same
sweep — and since the Mantel family and ANOSIM now run
their permutation loops over condensed storage too
(``kernels.permute_reduce`` closed-form triangle gathers), a
feature-backed session completes the ENTIRE analysis battery — PCoA,
PERMANOVA, PERMDISP, ANOSIM, Mantel, partial Mantel — with no n×n
distance matrix ever cached. The square builds are explicit opt-ins —
``gram`` for eigh/materialized ordination, and the ``"square"`` key
when the caller demands ``ws.dm`` itself — and, on a TPU, the Mantel
test's own hoist: its ``"rows"`` draw layout (``draw_layout``) builds
the centred x and ŷ square for the test. ``refresh()``
invalidates the whole cache (generation-counted) when the underlying
data changes.
"""

from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.config import ExecConfig
from repro.api.results import OrdinationResult
from repro.core.distance_matrix import DistanceMatrix, condensed_to_square
from repro.core.mantel import (MantelStatistic, condensed_moments_vec,
                               draw_layout)
from repro.core.operators import (CenteredGramOperator,
                                  CondensedCenteredGramOperator)
from repro.core.pcoa import pcoa as _pcoa
from repro.core.pcoa import resolve_dimensions
from repro.core.validation import ensure_finite
from repro.dist import get_metric, pairwise_condensed, takes_tree
from repro.dist.driver import check_tree, panel_feature_block
from repro.kernels.dispatch import HIGHEST
from repro.launch.mesh import chip_peaks
from repro.obs.ledger import FEATURE_HOIST_PASSES, HOIST_PASSES
from repro.obs.report import ObsSession, RunReport, build_report
from repro.obs.trace import NULL_OBS
from repro.stats import engine
from repro.stats.anosim import AnosimStatistic, rank_transform_condensed
from repro.stats.engine import PermutationTestResult, as_key
from repro.stats.partial_mantel import (PartialMantelPallasStatistic,
                                        PartialMantelStatistic)
from repro.stats.permanova import (PermanovaOperatorStatistic,
                                   PermanovaStatistic)
from repro.stats.permdisp import PermdispStatistic


class HoistCache:
    """Keyed store for a session's shared hoisted artifacts, instrumented
    with per-key hit/miss counters so "the O(n²) hoist ran exactly once"
    is a testable property, not a hope.

    Keys are either artifact names ("operator", "gram", "condensed",
    "ranks", "moments") or tuples whose first element is the artifact
    name (("coords", k, method, key-fingerprint)). ``misses[key]`` counts
    builds, ``hits[key]`` counts reuses.

    When a Workspace binds its ``ObsSession`` (``bind_obs``), every miss
    additionally runs under a ``hoist:<artifact>`` span and charges the
    session's analytic traffic ledger from the audited pass registry
    (``obs.ledger.HOIST_PASSES`` / ``FEATURE_HOIST_PASSES`` — the same
    table ``benchmarks/bench_api.py`` accounts with, so a ``RunReport``'s
    hoist totals reproduce the BENCH_api numbers live). Unbound caches
    talk to the no-op singleton: zero overhead, identical counters.
    """

    def __init__(self):
        self._store = {}
        self.hits = Counter()
        self.misses = Counter()
        self.obs = NULL_OBS
        self.n = 0
        self.pass_table = None

    def bind_obs(self, obs, n: int, table=None) -> "HoistCache":
        """Attach the observing session + the pass-table column (square-
        vs feature-backed) that prices this cache's builds."""
        self.obs = obs
        self.n = n
        self.pass_table = table
        return self

    def get(self, key, build):
        """The cached value for ``key``, building (and counting a miss) on
        first use."""
        if key in self._store:
            self.hits[key] += 1
        else:
            self.misses[key] += 1
            art = key if isinstance(key, str) else key[0]
            with self.obs.span(f"hoist:{art}", phase="hoist",
                               key=str(key), n=self.n):
                self._store[key] = build()
            self.obs.charge_hoist(art, self.n, table=self.pass_table)
        return self._store[key]

    def counts(self, key) -> tuple:
        """(hits, misses) for one key."""
        return self.hits[key], self.misses[key]

    def build_count(self, artifact: str) -> int:
        """Total builds of an artifact family (e.g. every ("coords", ...)
        entry counts toward "coords")."""
        return sum(c for k, c in self.misses.items()
                   if (k if isinstance(k, str) else k[0]) == artifact)

    def keys(self):
        return self._store.keys()

    def __contains__(self, key):
        return key in self._store

    def __len__(self):
        return len(self._store)

    # -- resident-set accounting -------------------------------------------
    def nbytes(self, key=None) -> int:
        """Resident bytes of one cached artifact, or of the whole cache.

        This is the currency of ``repro.serve``'s byte-budgeted session
        eviction: a pooled study's cost is exactly its HoistCache's
        resident set. With ``key=None`` the total deduplicates shared
        buffers (e.g. the operator holds a reference to the same
        condensed array the ``"condensed"`` entry stores — it is counted
        once); a per-key query counts that artifact's full reachable set.
        Unknown keys cost 0.
        """
        if key is not None:
            if key not in self._store:
                return 0
            return _resident_nbytes(self._store[key], set())
        return sum(self.nbytes_by_key().values())

    def nbytes_by_key(self) -> dict:
        """``{key: resident bytes}`` with shared buffers charged to the
        FIRST key (insertion order) that reaches them — so the values sum
        to the deduplicated total ``nbytes()`` returns."""
        seen: set = set()
        return {k: _resident_nbytes(v, seen)
                for k, v in self._store.items()}


def _resident_nbytes(value, seen: set) -> int:
    """Bytes of every array buffer reachable from ``value``, walking
    dicts/sequences/dataclasses (``OrdinationResult`` is a plain frozen
    dataclass, not a pytree, so ``tree_leaves`` would treat it as one
    opaque leaf — field recursion sees through it, and through the
    operator dataclasses alike). ``seen`` dedups by object identity
    across calls that share it."""
    if value is None or isinstance(value, (bool, int, float, complex, str,
                                           bytes)):
        return 0
    if id(value) in seen:
        return 0
    seen.add(id(value))
    nb = getattr(value, "nbytes", None)
    if isinstance(nb, (int, np.integer)):
        return int(nb)
    if isinstance(value, dict):
        return sum(_resident_nbytes(v, seen) for v in value.values())
    if isinstance(value, (list, tuple)):
        return sum(_resident_nbytes(v, seen) for v in value)
    if dataclasses.is_dataclass(value):
        return sum(_resident_nbytes(getattr(value, f.name), seen)
                   for f in dataclasses.fields(value))
    return 0


def _key_fingerprint(key) -> tuple:
    """Hashable identity of a PRNG key, for cache keys."""
    try:
        data = jax.random.key_data(key)
    except Exception:                    # raw uint32 key array
        data = key
    return tuple(int(v) for v in np.asarray(data).ravel())


class Workspace:
    """One distance matrix + one ExecConfig + a HoistCache = a session.

    ``dm`` may be a validated ``DistanceMatrix`` (trusted, per the paper's
    §4.3 validation caching) or a raw square array (validated here, once,
    via the fused single-pass check). The matrix is canonicalized to fp32
    and optionally pinned to ``config.device``; every analysis method then
    serves off the shared cache. See the module docstring for the artifact
    inventory.
    """

    def __init__(self,
                 dm: Union[DistanceMatrix, jax.Array, np.ndarray, None] = None,
                 config: Optional[ExecConfig] = None, validate: bool = True,
                 *, features=None, metric=None, tree=None):
        self.config = config if config is not None else ExecConfig()
        # the as-requested config survives resolution so refresh() (a new
        # n) re-solves from the user's intent, not a previous solution
        self.config_requested = self.config
        self.tuned = None
        self.generation = 0
        self.cache = HoistCache()
        # the observability session rides the whole Workspace lifetime
        # (spans accumulate across refresh() generations; each report
        # records the generation it snapshot). Disabled -> the shared
        # no-op singleton: every span/charge is a constant-time no-op.
        self._obs = (ObsSession(self.config.obs)
                     if self.config.obs.enabled else NULL_OBS)
        if features is not None:
            if dm is not None:
                raise ValueError("pass a distance matrix OR a feature "
                                 "table, not both")
            self._admit_features(features, metric, tree)
        else:
            if dm is None:
                raise ValueError("Workspace needs a distance matrix (or "
                                 "features= — see Workspace.from_features)")
            if tree is not None:
                raise ValueError("a tree goes with a feature table, not a "
                                 "distance matrix")
            self._admit_dm(dm, validate)
        self._resolve_config()
        self._bind_cache()

    @classmethod
    def from_features(cls, features, metric=None,
                      config: Optional[ExecConfig] = None, *,
                      tree=None) -> "Workspace":
        """A session straight from an (n, d) feature table — the fused
        ``repro.dist`` path.

        The distances are produced tile-by-tile in CONDENSED layout on
        first use, and the operator means are accumulated during that
        same sweep — so the whole
        analysis battery (``pcoa(method="fsvd")``, ``permanova``,
        ``permdisp``, ``anosim``, ``mantel``, ``partial_mantel``) runs
        without an n×n matrix of any kind ever existing: the permutation
        loops gather condensed storage by closed-form triangle indexing.
        The only square builds left are explicit opt-ins (``gram`` for
        eigh/materialized ordination; the lazily-counted ``"square"``
        key when ``ws.dm`` itself is demanded).

        ``metric`` is a ``repro.dist`` name or ``Metric`` instance
        (default: ``config.metric``, Bray–Curtis). The table is validated
        finite on admission (shared ``ensure_finite`` path) and
        canonicalized to fp32 like a distance matrix would be.

        A tree metric (``"unweighted_unifrac"``) takes the table's
        ``tree``, a ``repro.dist.PhyloTree`` with one tip per column;
        production then reads the table's branch embedding, made once
        per table (``repro.dist.tree_hoist``). A tree metric without a
        tree, a tree with any other metric, and a tree whose tip count is
        not the table's width are refused on admission.
        """
        return cls(features=features, metric=metric, config=config,
                   tree=tree)

    # -- admission (shared by __init__ and refresh) -------------------------
    def _admit_dm(self, dm, validate: bool) -> None:
        if not isinstance(dm, DistanceMatrix):
            arr = jnp.asarray(dm)
            # finite first: a NaN would otherwise surface as a baffling
            # "matrix is not symmetric" (NaN != NaN) — or, with
            # validate=False, propagate silently into eigenvalues
            ensure_finite(arr)
            dm = DistanceMatrix(arr, validate=validate)
        else:
            ensure_finite(dm.data)
            if validate and not dm._validated:
                # a DistanceMatrix built with validate=False is NOT trusted
                # just for its wrapper type — the session's validate flag
                # decides, exactly as for a raw array
                dm = DistanceMatrix(dm.data, ids=dm.ids, validate=True)
        data = dm.data
        if data.dtype != jnp.float32:
            data = data.astype(jnp.float32)
        if self.config.device is not None:
            data = jax.device_put(data, self.config.device)
        if data is dm.data and dm._validated:
            self._dm = dm
        else:
            # the session matrix is trusted once admitted — whether by the
            # validation pass above, by the source DistanceMatrix's own
            # cached validation, or by an explicit validate=False opt-out —
            # so downstream copies (e.g. inside pcoa) never revalidate
            self._dm = DistanceMatrix(data, ids=dm.ids,
                                      _skip_validation=True)
        self._features = None
        self._metric = None
        self._tree = None
        self.n = len(self._dm)

    def _admit_features(self, features, metric, tree) -> None:
        metric = get_metric(metric if metric is not None
                            else self.config.metric)
        with self._obs.span("ws.from_features") as span:
            with self._obs.span("ws.upload"):
                x = jnp.asarray(features)
            if x.ndim != 2:
                raise ValueError(f"expected an (n, d) feature table, "
                                 f"got shape {x.shape}")
            span.add(n=int(x.shape[0]), d=int(x.shape[1]))
            with self._obs.span("ws.validate"):
                # the finiteness check waits for the upload to land
                ensure_finite(x, what="feature table")
                if x.dtype != jnp.float32:
                    x = x.astype(jnp.float32)
                if self.config.device is not None:
                    x = jax.device_put(x, self.config.device)
        check_tree(metric, tree, int(x.shape[1]))
        self._features = x
        self._metric = metric
        self._tree = tree
        self._dm = None
        self.n = int(x.shape[0])

    # -- cache lifecycle ----------------------------------------------------
    def refresh(self, dm=None, *, features=None, metric=None,
                tree=None) -> "Workspace":
        """Invalidate every cached hoist and bump ``generation``.

        The HoistCache assumes the session matrix never changes under it;
        when it does — the caller mutated their source buffer, or wants to
        re-point the session at a new matrix/table — ``refresh`` is the
        documented way back to a consistent state: all cached artifacts
        (operator means, gram, ranks, coords, condensed, ...) are dropped
        with fresh hit/miss counters, and the next analysis re-runs each
        hoist exactly once. Pass ``dm=`` or ``features=`` to re-admit new
        data (same validation/canonicalization as construction); with no
        arguments the current matrix/table is kept and only the caches
        drop. New features keep the session's metric and, for a tree
        metric, its tree, unless ``metric=`` or ``tree=`` replace them.
        Returns ``self`` for chaining.
        """
        if dm is not None and features is not None:
            raise ValueError("pass a distance matrix OR a feature table, "
                             "not both")
        self.generation += 1
        self.cache = HoistCache()
        if dm is not None:
            self._admit_dm(dm, validate=True)
        elif features is not None:
            metric = metric if metric is not None else self._metric
            if (tree is None and metric is not None
                    and takes_tree(get_metric(metric))):
                tree = self._tree
            self._admit_features(features, metric, tree)
        elif self._features is not None:
            # feature-backed: the lazily-materialized square (if any) was
            # derived from the dropped production — it goes too
            self._dm = None
        self._resolve_config()
        self._bind_cache()
        return self

    def _resolve_config(self) -> None:
        """Materialize the requested config's auto knobs against the
        admitted data's (n, d) via ``repro.tune`` — ``self.config`` is
        always concrete after admission; ``self.config_requested`` keeps
        the user's intent and ``self.tuned`` the solver record (None
        when nothing asked for tuning)."""
        d = (int(self._features.shape[1]) if self._features is not None
             else None)
        self.config, self.tuned = self.config_requested.resolve(self.n, d)

    def _bind_cache(self) -> None:
        """Point the (fresh) HoistCache at the session's observability
        state and the pass-table column matching the current backing."""
        self.cache.bind_obs(
            self._obs, self.n,
            FEATURE_HOIST_PASSES if self._features is not None
            else HOIST_PASSES)

    # -- observability -------------------------------------------------------
    @property
    def obs(self):
        """The session's ``ObsSession`` (or the shared no-op singleton
        when ``config.obs.enabled`` is False)."""
        return self._obs

    def resolved_tiles(self) -> dict:
        """The tile geometry this session EXECUTES — post-tune (the
        solver's choices when auto) and post-snap (the shared
        ``kernels.dispatch`` lane rule at this backend/problem size) —
        as opposed to the requested knob values ``config`` carries.
        ``report()`` embeds this, so a RunReport records what actually
        ran."""
        from repro.kernels.dispatch import (lane_geometry, pick_block,
                                            snap_chunk)
        from repro.kernels.permute_reduce_ops import DEFAULT_CHUNK
        lane, floor = lane_geometry(self.config.interpret)
        m = self.n * (self.n - 1) // 2
        chunk = (self.config.chunk if self.config.chunk is not None
                 else DEFAULT_CHUNK)
        tiles = {
            "block": self.config.block,
            "block_executed": pick_block(self.n, self.config.block, lane,
                                         floor=floor),
            "feature_block": self.config.feature_block,
            "feature_block_executed": self._feature_block_executed(),
            "batch_size": self.config.resolve_batch_size(None, 32),
            "chunk": chunk,
            "chunk_executed": snap_chunk(m, chunk)[0],
            "lane": lane,
            "auto": self.tuned is not None,
            "draw_layout": self.draw_layout(),
        }
        return tiles

    def _feature_block_executed(self) -> int:
        """The feature chunk production reduces at once: the whole width
        of what the metric reads (a tree metric's branch embedding) for
        a product metric on the XLA path, else ``feature_block`` at most
        that width."""
        fb = self.config.feature_block
        if self._features is None:
            return fb
        width = (self._tree.num_branches if takes_tree(self._metric)
                 else int(self._features.shape[1]))
        fb = panel_feature_block(self._metric, self.config.pairwise_impl,
                                 width, fb)
        return max(min(fb, width), 1)

    def draw_layout(self) -> str:
        """The Mantel draws' layout at this session's n, tile and
        backend (``core.mantel.draw_layout``): ``"rows"`` on a TPU whose
        memory holds the squares, else ``"condensed"``."""
        backend = jax.default_backend()
        hbm = None
        if backend == "tpu":
            try:
                hbm = chip_peaks(jax.devices()[0].device_kind)["hbm_bytes"]
            except ValueError:   # no published peaks: keep the condensed loop
                pass
        return draw_layout(self.n, self.config.resolve_batch_size(None, 32),
                           backend, hbm)

    def report(self, meta: Optional[dict] = None) -> RunReport:
        """The session's ``RunReport``: span tree, analytic ledger
        totals, HoistCache hit/miss counters, the recompile sentinel's
        trace/program deltas for this session's window, and the
        resolved tile geometry (plus the full ``repro.tune`` record —
        chosen tiles, modeled bytes, budget — when the config was
        auto-solved). With observability disabled the report still
        carries the always-on telemetry (cache counters + the
        sentinel's process snapshot) with empty spans and ledger."""
        by_key = self.cache.nbytes_by_key()
        base = {"n": self.n, "generation": self.generation,
                "backing": ("features" if self._features is not None
                            else "distance_matrix"),
                "obs_enabled": self._obs.enabled,
                "tiles": self.resolved_tiles(),
                "cache_nbytes": {"total": sum(by_key.values()),
                                 "by_key": {str(k): v
                                            for k, v in by_key.items()}}}
        if self.tuned is not None:
            base["tune"] = self.tuned.to_dict()
        if meta:
            base.update(meta)
        measured = drift = None
        if self._obs.enabled and self.config.obs.probe:
            from repro.obs.drift import DriftSentinel
            from repro.obs.probe import probe_session
            measured = probe_session(self)
            drift = DriftSentinel().reconcile(measured)
        return build_report(self._obs if self._obs.enabled else None,
                            cache=self.cache, meta=base,
                            measured=measured, drift=drift)

    # -- canonical views ----------------------------------------------------
    @property
    def dm(self) -> DistanceMatrix:
        """The session's square DistanceMatrix. For a feature-backed
        session this MATERIALIZES the n×n square from the condensed
        production on first access (cache key ``"square"``) — no
        analysis method demands it anymore; it exists for callers who
        want the matrix itself (export, plotting, the distributed
        column-sharded paths)."""
        if self._dm is None:
            square = self.cache.get("square", lambda: condensed_to_square(
                self.condensed(), self.n))
            self._dm = DistanceMatrix(square, _skip_validation=True)
        return self._dm

    @property
    def data(self) -> jax.Array:
        return self.dm.data

    # -- shared hoisted artifacts -------------------------------------------
    def _produce_distances(self) -> None:
        """Run the tiled ``repro.dist`` production (feature-backed sessions
        only): ONE sweep over the feature table builds BOTH cache entries —
        ``"condensed"`` (the pdist-layout distances) and ``"dist_means"``
        (the operator row/global means, accumulated while each tile was
        resident). The two keys miss together, by
        construction."""
        if "condensed" in self.cache and "dist_means" in self.cache:
            return
        with self._obs.span("ws.produce_distances", phase="production",
                            n=self.n, d=int(self._features.shape[1]),
                            metric=self._metric.name,
                            impl=self.config.pairwise_impl):
            prod = pairwise_condensed(
                self._features, self._metric, block=self.config.block,
                feature_block=self.config.feature_block,
                impl=self.config.pairwise_impl,
                interpret=self.config.interpret, tree=self._tree)
        self.cache.get("condensed", lambda: prod["condensed"])
        self.cache.get("dist_means", lambda: {
            k: prod[k] for k in ("row_means", "global_mean")})

    def condensed(self) -> jax.Array:
        """The condensed (scipy ``pdist`` layout) distances. Feature-backed
        sessions produce them tile-by-tile (never a square); square-backed
        sessions extract the upper triangle once."""
        if self._features is not None:
            self._produce_distances()
            return self.cache.get("condensed", lambda: None)
        return self.cache.get("condensed",
                              lambda: self._dm.condensed_form())

    def operator(self):
        """The matrix-free centered-Gram operator: row/global means of
        E = −½D∘D hoisted in ONE read of D — or, for a feature-backed
        session, taken for FREE from the production sweep's fused
        accumulators and served over the condensed storage."""
        if self._features is not None:
            def build():
                self._produce_distances()
                means = self.cache.get("dist_means", lambda: None)
                return CondensedCenteredGramOperator(
                    self.cache.get("condensed", lambda: None),
                    means["row_means"], means["global_mean"], self.n,
                    self.config.block)
            return self.cache.get("operator", build)
        return self.cache.get("operator", lambda: (
            CenteredGramOperator.from_distance(
                self.data, block=self.config.block,
                impl=self.config.matvec_impl,
                interpret=self.config.interpret)))

    def gram(self) -> jax.Array:
        """The materialized Gower-centered matrix (PERMANOVA's hoist; the
        eigh / materialized-ordination paths), via config.centering_impl."""
        from repro.core.pcoa import materialized_gram
        return self.cache.get("gram", lambda: materialized_gram(
            self.data, self.config.centering_impl, self.config.mesh))

    def ranks(self) -> dict:
        """ANOSIM's rank transform: the O(m log m) sort, run once — and
        kept CONDENSED: the batched permutation loop gathers the
        condensed within-indicator, so no square rank matrix exists
        anywhere. Both backings rank the shared ``"condensed"`` artifact
        (for a square-backed session that is one cached triangle
        extraction, also reused by ``moments``)."""
        return self.cache.get("ranks", lambda: rank_transform_condensed(
            self.condensed()))

    def moments(self) -> dict:
        """Condensed normalization moments (centered norm + the
        centered-normalized vector, O(m)) — the shared currency of BOTH
        Mantel-family sides: the permuted side consumes ``norm``, a fixed
        side contributes its ``hat`` vector directly (condensed — since
        the batched loop gathers condensed storage, no square hat form
        exists anymore). Both backings take them from the shared
        ``"condensed"`` artifact in two passes (mean, then the centered
        norm): the one-pass Σd² − m·mean² form cancels in fp32 and moved
        the observed Mantel r by 2.2e-4 at n=4096."""
        return self.cache.get("moments", lambda: condensed_moments_vec(
            self.condensed()))

    # -- analyses -----------------------------------------------------------
    def pcoa(self, dimensions: int = 10, method: str = "fsvd",
             key=None) -> OrdinationResult:
        """Principal Coordinates Analysis off the cached operator/gram.

        Full ``OrdinationResult`` objects are cached per
        (dimensions, method, key), so ``ws.permdisp`` reuses the exact
        coordinates a previous ``ws.pcoa`` produced. An ``eigh`` request
        for k dimensions is additionally served by SLICING any cached
        higher-k eigh solution (the exact solver computes the full
        spectrum and keeps the top k, so the slice is bitwise what a
        direct solve would return) — counted as a hit on the higher-k
        entry, no re-solve. (fsvd can't be sliced: its sketch width is
        k-dependent.)
        """
        k = resolve_dimensions(dimensions, self.n)
        key = as_key(key, default=42)
        fp = _key_fingerprint(key) if method == "fsvd" else None
        cache_key = ("coords", k, method, fp)

        def build():
            if method == "eigh" or (method == "fsvd"
                                    and self.config.materialize):
                return _pcoa(self.dm, dimensions=k, method=method, key=key,
                             config=self.config, check_finite=False,
                             gram=self.gram())
            # matrix-free paths — including the distributed matvec, whose
            # exact trace() comes off the same hoisted means. A feature-
            # backed session passes dm=None: fully matrix-free off the
            # condensed operator (the distributed matvec still needs the
            # square, so it goes through self.dm).
            dm = self.dm if self.config.centering_impl == "distributed" \
                else self._dm
            return _pcoa(dm, dimensions=k, method=method, key=key,
                         config=self.config, check_finite=False,
                         operator=self.operator())

        if method == "eigh" and cache_key not in self.cache:
            cands = [kk for kk in self.cache.keys()
                     if isinstance(kk, tuple) and kk[0] == "coords"
                     and kk[2] == "eigh" and kk[1] >= k]
            if cands:
                src = min(cands, key=lambda kk: kk[1])
                full = self.cache.get(src, lambda: None)  # reuse: a hit

                def build():    # noqa: F811 — slice, don't re-solve
                    return OrdinationResult(
                        coordinates=full.coordinates[:, :k],
                        eigenvalues=full.eigenvalues[:k],
                        proportion_explained=full.proportion_explained[:k],
                        method="eigh", key=None)

        with self._obs.span("ws.pcoa", n=self.n, dimensions=k,
                            method=method):
            return self.cache.get(cache_key, build)

    # -- statistic construction (the serve seam) -----------------------------
    def statistic(self, method: str, *, grouping=None, other=None,
                  control=None, dimensions: Optional[int] = None,
                  pcoa_method: str = "fsvd"):
        """Build the hoisted ``(statistic, default_alternative)`` pair for
        one permutation test, without running the Monte-Carlo loop.

        This is the seam the analysis methods below and the
        ``repro.serve`` scheduler share: the statistic carries every
        cached hoist (so constructing it triggers at most the session's
        one-time artifact builds), and the caller decides how to drive
        the loop — ``engine.permutation_test`` for a whole test here,
        ``engine.hoist_and_observe`` + ``engine.tile_statistics`` for the
        front door's coalesced tiles. ``default_alternative`` is the
        test's canonical sidedness ("greater" for the grouping tests,
        "two-sided" for the Mantel family).
        """
        if method == "permanova":
            # a feature-backed session runs the OPERATOR form: the
            # per-permutation quadratic forms stream op.matvec(Z_p) off
            # the condensed storage, so neither the square D nor the
            # square Gower matrix is ever materialized
            # (config.materialize=True restores the materialized baseline)
            codes, num_groups = self._codes(grouping)
            if self._features is not None and not self.config.materialize:
                return PermanovaOperatorStatistic(
                    self.operator(), codes, self.n, num_groups), "greater"
            return PermanovaStatistic(self.data, codes, self.n, num_groups,
                                      pre={"g": self.gram()}), "greater"
        if method == "anosim":
            # ranks stay condensed end to end; the statistic's dm field is
            # only consumed when no pre-hoisted ranks are supplied
            codes, num_groups = self._codes(grouping)
            return AnosimStatistic(None, codes, self.n, num_groups,
                                   pre=self.ranks(),
                                   kernel=self.config.kernel,
                                   interpret=self.config.interpret,
                                   chunk=self.config.chunk), "greater"
        if method == "permdisp":
            codes, num_groups = self._codes(grouping)
            dims = resolve_dimensions(dimensions, self.n)
            coords = self.pcoa(dimensions=dims,
                               method=pcoa_method).coordinates
            return PermdispStatistic(coords, codes, self.n,
                                     num_groups), "greater"
        if method == "mantel":
            y = self._coerce(other)
            if y.n != self.n:
                raise ValueError("x and y must have the same shape")
            pre = {"normxm": self.moments()["norm"],
                   "ynorm": y.moments()["hat"]}
            return MantelStatistic(self.condensed(), None, self.n, pre=pre,
                                   kernel=self.config.kernel,
                                   interpret=self.config.interpret,
                                   chunk=self.config.chunk,
                                   layout=self.draw_layout()), "two-sided"
        if method == "partial_mantel":
            y, z = self._coerce(other), self._coerce(control)
            if not (self.n == y.n == z.n):
                raise ValueError("x, y and z must have the same shape")
            xm, ym, zm = self.moments(), y.moments(), z.moments()
            r_yz = jnp.dot(ym["hat"], zm["hat"], precision=HIGHEST)
            r_xz = jnp.dot(xm["hat"], zm["hat"], precision=HIGHEST)
            # eager degeneracy checks (can't raise inside the jitted
            # engine): |r_yz|→1 makes the residualization 0/0, NaN-ing
            # the whole null; |r_xz|→1 makes the observed statistic 0/0.
            # 1e-5, not 1e-6: an fp32 self-correlation rounds to 1-r² as
            # large as ~1e-6, and any genuine r this close is numerically
            # useless
            for pair, r in (("y and z", float(r_yz)),
                            ("x and z", float(r_xz))):
                if 1.0 - r * r < 1e-5:
                    raise ValueError(
                        f"{pair} are (nearly) collinear (r={r:.6f}); the "
                        f"partial correlation is undefined — use the "
                        f"plain Mantel test")
            denom = jnp.sqrt(1.0 - r_yz * r_yz)
            pre = {"normxm": xm["norm"], "r_yz": r_yz,
                   "y_res": (ym["hat"] - r_yz * zm["hat"]) / denom,
                   "z": zm["hat"]}
            # fixed sides ride in via pre only (their y/z fields are
            # consumed solely by the no-pre hoist) — nothing square for
            # any operand
            cls = (PartialMantelPallasStatistic
                   if self.config.kernel == "pallas"
                   else PartialMantelStatistic)
            return cls(self.condensed(), None, None, self.n, pre=pre,
                       kernel=self.config.kernel,
                       interpret=self.config.interpret,
                       chunk=self.config.chunk), "two-sided"
        raise ValueError(
            f"unknown method {method!r}; expected one of ('permanova', "
            f"'anosim', 'permdisp', 'mantel', 'partial_mantel')")

    def permanova(self, grouping, permutations: int = 999, key=None,
                  batch_size: Optional[int] = None) -> PermutationTestResult:
        """PERMANOVA off the cached Gower centering (one-sided, greater).

        A feature-backed session runs the OPERATOR form instead: the
        per-permutation quadratic forms stream ``op.matvec(Z_p)`` off the
        condensed storage, so neither the square D nor the square Gower
        matrix is ever materialized (``config.materialize=True`` restores
        the materialized-gram baseline)."""
        with self._obs.span("ws.permanova", n=self.n,
                            permutations=permutations):
            stat, alt = self.statistic("permanova", grouping=grouping)
            return engine.permutation_test(
                stat, permutations, key, alternative=alt,
                batch_size=self.config.resolve_batch_size(batch_size, 32),
                config=self.config, method="permanova")

    def anosim(self, grouping, permutations: int = 999, key=None,
               batch_size: Optional[int] = None) -> PermutationTestResult:
        """ANOSIM off the cached rank transform (one-sided, greater).

        The ranks stay condensed end to end: the batched loop gathers
        the condensed within-indicator by closed-form triangle indexing,
        so neither backing ever materializes a square rank matrix."""
        with self._obs.span("ws.anosim", n=self.n,
                            permutations=permutations,
                            kernel=self.config.kernel):
            stat, alt = self.statistic("anosim", grouping=grouping)
            return engine.permutation_test(
                stat, permutations, key, alternative=alt,
                batch_size=self.config.resolve_batch_size(batch_size, 32),
                config=self.config, method="anosim")

    def permdisp(self, grouping, permutations: int = 999, key=None,
                 dimensions: Optional[int] = None, method: str = "fsvd",
                 batch_size: Optional[int] = None) -> PermutationTestResult:
        """PERMDISP off the cached ordination (one-sided, greater).

        The coordinate hoist is shared with ``ws.pcoa`` at matching
        (dimensions, method) — the whole ordination is computed at most
        once per session."""
        dims = resolve_dimensions(dimensions, self.n)
        with self._obs.span("ws.permdisp", n=self.n,
                            permutations=permutations, dimensions=dims):
            stat, alt = self.statistic("permdisp", grouping=grouping,
                                       dimensions=dims, pcoa_method=method)
            return engine.permutation_test(
                stat, permutations, key, alternative=alt,
                batch_size=self.config.resolve_batch_size(batch_size, 32),
                config=self.config, method="permdisp")

    def mantel(self, other, permutations: int = 999, key=None,
               alternative: str = "two-sided",
               batch_size: Optional[int] = None) -> PermutationTestResult:
        """Mantel test of this matrix (permuted side) against ``other``
        (a Workspace, DistanceMatrix or raw array; held fixed). The
        permuted side rides in as the shared condensed artifact and the
        fixed side as its CONDENSED hat vector — neither session ever
        demands the lazy ``"square"`` key. The draws take the layout of
        ``draw_layout()``: ``"condensed"`` (closed-form triangle gathers
        over the condensed entries, nothing square; every backend but the
        TPU, and any n whose squares do not fit) or ``"rows"`` (a TPU
        whose memory holds them: the test's hoist builds the centred x
        and ŷ as two hollow squares, and each draw gathers their rows)."""
        with self._obs.span("ws.mantel", n=self.n,
                            permutations=permutations,
                            kernel=self.config.kernel):
            stat, _ = self.statistic("mantel", other=other)
            return engine.permutation_test(
                stat, permutations, key, alternative=alternative,
                batch_size=self.config.resolve_batch_size(batch_size, 32),
                config=self.config, method="mantel")

    def partial_mantel(self, other, control, permutations: int = 999,
                       key=None, alternative: str = "two-sided",
                       batch_size: Optional[int] = None
                       ) -> PermutationTestResult:
        """Partial Mantel of this matrix against ``other``, controlling
        for ``control``; ŷ is residualized from cached moments — all
        three operands stay condensed (``mantel``'s condensed layout).
        Routes through the Pallas ``permute_reduce`` backend when
        ``config.kernel == "pallas"``."""
        with self._obs.span("ws.partial_mantel", n=self.n,
                            permutations=permutations,
                            kernel=self.config.kernel):
            stat, _ = self.statistic("partial_mantel", other=other,
                                     control=control)
            return engine.permutation_test(
                stat, permutations, key, alternative=alternative,
                batch_size=self.config.resolve_batch_size(batch_size, 32),
                config=self.config, method="partial_mantel")

    # -- plumbing -----------------------------------------------------------
    def _codes(self, grouping):
        codes, num_groups = engine.encode_grouping(grouping)
        if codes.size != self.n:
            raise ValueError("grouping length does not match distance "
                             "matrix")
        return jnp.asarray(codes), num_groups

    def _coerce(self, other) -> "Workspace":
        """Other operands join the session: an existing Workspace keeps its
        own cache; anything else gets a one-shot Workspace on this
        session's config. A DistanceMatrix's validation status is trusted
        as constructed (paper §4.3 — exactly what the pre-session free
        functions did); raw arrays are validated on admission."""
        if isinstance(other, Workspace):
            return other
        return Workspace(other, config=self.config,
                         validate=not isinstance(other, DistanceMatrix))

    def __repr__(self):
        return (f"Workspace(n={self.n}, cached={sorted(map(str, self.cache.keys()))}, "
                f"config={self.config})")
