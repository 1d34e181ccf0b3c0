"""Principal Coordinates Analysis: paper §4.1, operator-based.

``pcoa = centering + eigendecomposition`` — and since PR 2 the two halves
are *fused*: the default path never materializes the Gower-centered matrix
at all. The paper's finding was that centering dominated runtime because it
is pure off-chip traffic; the operator architecture finishes that argument
by deleting the n² write (and the solver's k re-reads) entirely:

* ``core.operators.CenteredGramOperator`` hoists the row/global means of
  ``E = −½D∘D`` in one read of D and applies
  ``F @ X = E@X − r(1ᵀX) − 1(rᵀX) + m·1(1ᵀX)`` to skinny (n, k+p) blocks,
  with the E-formation fused into each row-blocked matmul (XLA) or
  VMEM-tiled in-register (``kernels.center_matvec``, ``matvec_impl=
  "pallas"``). Sfiligoi et al. 2021 ("Enabling microbiome research on
  personal devices") make the same point from the footprint side: dropping
  the materialized intermediate is what lets large-cohort ordination fit
  on small machines.
* ``method="fsvd"`` — randomized range-finder with power iterations
  (Halko et al. 2011, Algs. 4.3/5.3) driven entirely through
  ``operator.matvec``; ``materialize=True`` restores the old
  materialize-then-solve path (the perf baseline in ``--suite pcoa``).
* ``method="eigh"`` — exact symmetric eigendecomposition: the oracle. It
  needs the full matrix, so it always materializes (via ``centering_impl``:
  "ref" / "fused" / "distributed").
* ``centering_impl="distributed"`` with ``materialize=False`` routes each
  matvec through the shard_map mesh layout of ``core.centering``
  (``operators.centered_gram_matvec_distributed``) — no n² tensor crosses
  the interconnect, or even exists per device beyond the D blocks.

Output mirrors scikit-bio's ``OrdinationResults``: coordinates scaled by
√λ, eigenvalues, and the proportion of variance explained. Convention for
non-Euclidean distances (which Gower centering can take to negative
eigenvalues): the numerator clamps negative eigenvalues to zero, as
scikit-bio does, while the denominator is the **exact** total inertia
``Σλ = tr(F)`` from ``operator.trace()`` — previously a materialized
``jnp.trace`` whose ``total <= 0`` fallback silently renormalized by only
the top-k inertia. ``tr(F) ≥ 0`` always (E ≤ 0 entrywise), with equality
only for the all-zero matrix, where the proportions are defined as 0.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.api.config import ExecConfig
from repro.api.results import OrdinationResult
from repro.core import centering
from repro.core.distance_matrix import DistanceMatrix
from repro.core.operators import (CenteredGramOperator,
                                  centered_gram_matvec_distributed)
from repro.kernels.dispatch import HIGHEST
from repro.obs.compile import note_run, note_trace
from repro.obs.trace import current_obs

# Legacy name for the unified ordination result (same class; the api
# redesign moved it to repro.api.results and added the recorded RNG key).
PCoAResults = OrdinationResult


def resolve_dimensions(dimensions: Optional[int], n: int) -> int:
    """THE validation rule for requested ordination dimensionality.

    ``None`` means "all axes" (n - 1, scikit-bio's PERMDISP convention);
    ``dimensions <= 0`` raises; ``dimensions > n`` clamps to n. Both the
    fsvd and eigh paths (and permdisp's forwarding) route through this one
    helper — previously fsvd and eigh diverged on non-positive input
    (negative k silently sliced from the *bottom* of the spectrum).
    """
    if dimensions is None:
        return max(n - 1, 1)
    d = int(dimensions)
    if d != dimensions:
        raise ValueError(f"dimensions must be an integer, got {dimensions!r}")
    if d <= 0:
        raise ValueError(f"dimensions must be positive, got {d}")
    return min(d, n)


# --------------------------------------------------------------------------
# Randomized eigensolver (Halko et al. 2011) — matvec-driven
# --------------------------------------------------------------------------
def _subspace_iteration(matvec, n: int, dtype, key, k: int, oversample: int,
                        power_iters: int):
    """Top-k eigenpairs of a symmetric operator given only ``matvec``.

    Range finder: Y = A Ω, orthonormalize, power-iterate (A symmetric ⇒
    AᵀA = A²); project T = QᵀAQ (small, (k+p)²); exact eigh of T lifts
    back. Every O(n²k)-flop step is a single fused matvec — the operator
    decides whether that is a sharded matmul, a row-blocked XLA sweep or
    the Pallas kernel. Profiler scope ``pcoa.solve``.
    """
    with jax.named_scope("pcoa.solve"):
        p = min(k + oversample, n)
        omega = jax.random.normal(key, (n, p), dtype=dtype)
        q, _ = jnp.linalg.qr(matvec(omega))
        for _ in range(power_iters):
            q, _ = jnp.linalg.qr(matvec(q))
        t = jnp.matmul(q.T, matvec(q), precision=HIGHEST)   # (p, p), tiny
        t = 0.5 * (t + t.T)
        evals, evecs = jnp.linalg.eigh(t)
        # eigh returns ascending; take top-k by value (descending)
        order = jnp.argsort(-evals)[:k]
        return evals[order], jnp.matmul(q, evecs,
                                        precision=HIGHEST)[:, order]


@partial(jax.jit, static_argnames=("k", "oversample", "power_iters"))
def _randomized_eigh_matfree(op: CenteredGramOperator, key, k: int,
                             oversample: int = 10, power_iters: int = 2):
    """Matrix-free fsvd: the operator pytree crosses the jit boundary with
    its tiling metadata static, so repeated solves of one shape reuse the
    executable."""
    note_trace("pcoa.fsvd_matfree", (op.n, k, oversample, power_iters),
               _randomized_eigh_matfree, (op, key),
               {"k": k, "oversample": oversample, "power_iters": power_iters})
    return _subspace_iteration(op.matvec, op.n, op.dtype, key, k,
                               oversample, power_iters)


@partial(jax.jit, static_argnames=("k", "oversample", "power_iters"))
def _randomized_eigh(a: jax.Array, key, k: int, oversample: int = 10,
                     power_iters: int = 2):
    """Materialized fsvd — the baseline the benchmarks race against."""
    note_trace("pcoa.fsvd_materialized",
               (a.shape[0], k, oversample, power_iters))
    return _subspace_iteration(lambda x: jnp.matmul(a, x, precision=HIGHEST),
                               a.shape[0], a.dtype, key, k,
                               oversample, power_iters)


@partial(jax.jit, static_argnames=("k",))
def _exact_eigh(a: jax.Array, k: int):
    note_trace("pcoa.eigh", (a.shape[0], k))
    evals, evecs = jnp.linalg.eigh(a)
    order = jnp.argsort(-evals)[:k]
    return evals[order], evecs[:, order]


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------
def materialized_gram(dm_data: jax.Array, centering_impl: str = "fused",
                      mesh=None) -> jax.Array:
    """The full Gower-centered matrix via the selected centering impl —
    the one entry point PERMANOVA's hoist and the eigh/materialized
    ordination paths share (so a Workspace can cache exactly one)."""
    if centering_impl == "ref":
        return centering.center_distance_matrix_ref(dm_data)
    if centering_impl == "fused":
        return centering.center_distance_matrix(dm_data)
    if centering_impl == "distributed":
        if mesh is None:
            raise ValueError("distributed centering requires a mesh")
        return centering.center_distance_matrix_distributed(dm_data, mesh)
    raise ValueError(f"unknown centering_impl {centering_impl!r}")


def pcoa(dm: Optional[DistanceMatrix], dimensions: int = 10,
         method: str = "fsvd", key=None, mesh=None,
         centering_impl: str = "fused", materialize: bool = False,
         matvec_impl: str = "xla", block: int = 256,
         config: Optional[ExecConfig] = None,
         operator: Optional[CenteredGramOperator] = None,
         gram: Optional[jax.Array] = None,
         check_finite: bool = True) -> OrdinationResult:
    """Principal Coordinates Analysis of a distance matrix.

    ``method="fsvd"`` (default) runs **matrix-free** against a
    ``CenteredGramOperator`` — no n×n intermediate is ever written; pass
    ``materialize=True`` for the legacy materialize-then-solve path (the
    benchmark baseline). ``method="eigh"`` is the exact oracle and always
    materializes.

    Execution knobs resolve from ``config`` (an ``api.ExecConfig``) when
    given; the legacy kwargs (``mesh``/``centering_impl``/``materialize``/
    ``matvec_impl``/``block``) are kept for compatibility and are ignored
    when ``config`` is present. ``key`` accepts a PRNG key or int seed
    (``stats.engine.as_key``; None -> the documented seed 42). A Workspace
    passes its cached ``operator`` (matrix-free paths) or ``gram`` (the
    materialized Gower matrix, eigh/materialized paths) so the O(n²)
    hoists run once per session, not once per call; ``dimensions`` is
    validated by ``resolve_dimensions`` (<= 0 raises, > n clamps)
    identically on every path.

    ``dm=None`` is the fully matrix-free entry: a prebuilt ``operator``
    (e.g. the condensed-backed one ``Workspace.from_features`` hoists
    straight out of the ``repro.dist`` tile sweep) stands in for the
    square matrix entirely — only legal for the matrix-free fsvd path,
    since eigh/materialized solves need an actual matrix. Non-finite
    input is rejected up front (``check_finite=False`` for callers that
    already validated, e.g. a Workspace session): a NaN in D otherwise
    propagates silently into the eigenvalues.
    """
    from repro.core.validation import ensure_finite
    from repro.stats.engine import as_key
    cfg = config if config is not None else ExecConfig(
        mesh=mesh, centering_impl=centering_impl, materialize=materialize,
        matvec_impl=matvec_impl, block=block)
    key = as_key(key, default=42)

    if dm is None:
        if operator is None:
            raise ValueError("pcoa needs a DistanceMatrix or a prebuilt "
                             "operator")
        if method != "fsvd" or cfg.materialize or \
                cfg.centering_impl == "distributed":
            raise ValueError("dm=None (operator-only) is limited to the "
                             "matrix-free fsvd path; eigh/materialized/"
                             "distributed solves need the square matrix")
    elif check_finite:
        ensure_finite(dm.data)

    def _gram(data):
        return gram if gram is not None else \
            materialized_gram(data, cfg.centering_impl, cfg.mesh)

    # a prebuilt artifact the taken path would ignore is a caller error —
    # silently dropping the O(n²) hoist they paid for would defeat the
    # entire point of passing it
    needs_gram = method == "eigh" or (method == "fsvd" and cfg.materialize)
    if gram is not None and not needs_gram:
        raise ValueError("a prebuilt gram is only consumed by eigh / "
                         "materialized paths; this call runs matrix-free "
                         "(pass operator= instead)")
    if operator is not None and needs_gram:
        raise ValueError("a prebuilt operator is only consumed by the "
                         "matrix-free fsvd path (pass gram= instead)")

    if dm is not None:
        # scikit-bio's pcoa makes an internal copy of the DistanceMatrix —
        # the paper's validation-caching means this copy is free of
        # revalidation.
        dm = dm.copy()
        n = len(dm)
    else:
        n = operator.n
    k = resolve_dimensions(dimensions, n)

    if method not in ("eigh", "fsvd"):
        raise ValueError(f"unknown method {method!r}")
    with current_obs().span(f"pcoa.{method}", phase="solve", n=n, k=k,
                            materialize=cfg.materialize,
                            impl=cfg.matvec_impl):
        if method == "eigh":
            centered = _gram(dm.data)
            evals, evecs = _exact_eigh(centered, k)
            total = jnp.trace(centered)      # exact: the matrix exists
            key = None                       # deterministic — no RNG used
        elif cfg.materialize:
            centered = _gram(dm.data)
            evals, evecs = _randomized_eigh(centered, key, k)
            total = jnp.trace(centered)
        elif cfg.centering_impl == "distributed":
            if cfg.mesh is None:
                raise ValueError("distributed matvec requires a mesh")
            evals, evecs = _subspace_iteration(
                lambda x: centered_gram_matvec_distributed(dm.data, x,
                                                           cfg.mesh),
                n, dm.data.dtype, key, k, oversample=10, power_iters=2)
            total = (operator if operator is not None else
                     CenteredGramOperator.from_distance(dm.data)).trace()
        else:
            op = operator if operator is not None else \
                CenteredGramOperator.from_distance(
                    dm.data, block=cfg.block, impl=cfg.matvec_impl,
                    interpret=cfg.interpret)
            # the signature its trace note records, at the default sketch
            note_run(_randomized_eigh_matfree, (op.n, k, 10, 2))
            evals, evecs = _randomized_eigh_matfree(op, key, k)
            total = op.trace()

    pos = jnp.maximum(evals, 0.0)
    coordinates = evecs * jnp.sqrt(pos)[None, :]
    # proportion explained: clamped eigenvalues over the EXACT total
    # inertia Σλ = tr(F) — from the operator's hoisted sums on matrix-free
    # paths, jnp.trace of the already-materialized matrix otherwise. With
    # fsvd only k eigenvalues are known, so a top-k denominator would
    # silently overstate every proportion. tr(F) = 0 only for the all-zero
    # matrix.
    proportion = jnp.where(total > 0, pos / total, jnp.zeros_like(pos))
    return OrdinationResult(coordinates=coordinates, eigenvalues=evals,
                            proportion_explained=proportion, method=method,
                            key=key)
