"""Matrix-free centered-Gram operators: the §4.1 traffic argument, finished.

``pcoa`` historically materialized the full Gower-centered matrix
``F = E − r·1ᵀ − 1·rᵀ + m`` (with ``E = −½ D∘D``, ``r`` its row means and
``m`` its global mean) before the randomized eigensolver touched it — one
whole n² write plus k re-reads of off-chip traffic. But the Halko solver
only ever consumes ``F`` through products with skinny (n, k+p) blocks, and
every term of ``F`` is cheap to apply on the fly:

    F @ X = E @ X − r (1ᵀX) − 1 (rᵀX) + m·1 (1ᵀX)

``CenteredGramOperator`` hoists ``r`` and ``m`` once (a single read of D,
no n² intermediate: the row means of E are ``−½·mean(D∘D, axis=1)``, which
XLA fuses into the reduction) and then exposes:

* ``matvec(x)``   — ``F @ x`` with the elementwise E-formation and the
  rank-1 centering corrections fused into each row-blocked matmul; peak
  extra memory is one (block, n) strip, never n².
* ``trace()``     — the exact total inertia Σλ from the hoisted sums:
  ``tr(F) = tr(E) − n·m`` (and ``tr(E) = 0`` for a hollow D), so
  ``proportion_explained`` needs no materialized matrix.
* ``materialize()`` — the full F via ``core.centering`` (the eigh oracle
  path).

``centered_gram_matvec_distributed`` is the pod-scale analogue: the same
matvec through the 2-D block-sharded mesh layout of
``core.centering.center_distance_matrix_distributed`` — only O(n·k) bytes
ever cross the interconnect.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.distance_matrix import MAX_TRIANGLE_N, condensed_index
from repro.kernels.dispatch import HIGHEST


@partial(jax.tree_util.register_dataclass,
         data_fields=["d", "row_means", "global_mean"],
         meta_fields=["n", "block", "impl", "interpret"])
@dataclasses.dataclass
class CenteredGramOperator:
    """The Gower-centered Gram matrix of a distance matrix, as an operator.

    A pytree (``register_dataclass``) so it can cross ``jax.jit`` boundaries:
    ``d``/``row_means``/``global_mean`` are traced, the tiling metadata is
    static. ``impl`` selects the matvec backend: ``"xla"`` (row-blocked jnp,
    the default) or ``"pallas"`` (the VMEM-tiled ``kernels.center_matvec``).
    """

    d: jax.Array            # (n, n) distance matrix — the ONLY n² buffer
    row_means: jax.Array    # (n,)  row means of E = −½ D∘D (== col means)
    global_mean: jax.Array  # ()    global mean of E
    n: int
    block: int = 256
    impl: str = "xla"
    interpret: Optional[bool] = None    # Pallas only; None = auto by backend

    @classmethod
    def from_distance(cls, d: jax.Array, *, block: int = 256,
                      impl: str = "xla",
                      interpret: Optional[bool] = None) -> "CenteredGramOperator":
        """Hoist r and m in one read of D — no n² intermediate is written."""
        if impl not in ("xla", "pallas"):
            raise ValueError(f"unknown matvec impl {impl!r}")
        n = d.shape[0]
        # mean-of-square fuses the elementwise map into the row reduction
        row_means = -0.5 * jnp.mean(d * d, axis=1)
        return cls(d, row_means, jnp.mean(row_means), n, block, impl,
                   interpret)

    @property
    def dtype(self):
        return self.d.dtype

    # -- the operator interface --------------------------------------------
    def matvec(self, x: jax.Array) -> jax.Array:
        """``F @ x`` without materializing F (or even E).

        ``x``: (n, k) block (a 1-D vector is promoted and squeezed back).
        The rank-1 corrections cost O(nk); the E product is applied one
        (block, n) row strip at a time so the elementwise −½D∘D feeds the
        matmul straight from registers/cache.
        """
        squeeze = x.ndim == 1
        if squeeze:
            x = x[:, None]
        if self.impl == "pallas":
            # the kernel wrapper hoists its own correction vectors
            from repro.kernels.center_matvec_ops import center_matvec_pallas
            out = center_matvec_pallas(
                self.d, x, self.row_means, self.global_mean,
                block_m=self.block, block_n=self.block,
                interpret=self.interpret)
        else:
            colsum = jnp.sum(x, axis=0)                  # 1ᵀX   (k,)
            corr = self.global_mean * colsum - jnp.matmul(
                self.row_means, x, precision=HIGHEST)    # m·1ᵀX − rᵀX
            b = max(min(self.block, self.n), 1)
            parts = []
            for i0 in range(0, self.n, b):               # static row strips
                rows = self.d[i0:i0 + b]
                e_rows = -0.5 * rows * rows              # fused into the dot
                parts.append(jnp.matmul(e_rows, x, precision=HIGHEST)
                             - self.row_means[i0:i0 + b, None] * colsum[None, :]
                             + corr[None, :])
            out = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
        return out[:, 0] if squeeze else out

    def trace(self) -> jax.Array:
        """Exact ``tr(F) = Σλ`` from the hoisted sums — no matrix needed.

        ``tr(F) = tr(E) − 2·Σr + n·m`` and ``Σr = n·m``, so
        ``tr(F) = tr(E) − n·m``; for a hollow D, ``tr(E) = 0`` and the
        total inertia is simply ``−n·m`` (≥ 0, since E ≤ 0 entrywise).
        The diagonal term is kept for robustness to non-hollow input.
        """
        tr_e = -0.5 * jnp.sum(jnp.diagonal(self.d) ** 2)
        return tr_e - self.n * self.global_mean

    def materialize(self) -> jax.Array:
        """The full F — the oracle path (``method="eigh"`` needs it)."""
        from repro.core.centering import center_distance_matrix
        return center_distance_matrix(self.d)


# --------------------------------------------------------------------------
# Condensed-backed operator — the repro.dist fusion target
# --------------------------------------------------------------------------
@partial(jax.tree_util.register_dataclass,
         data_fields=["dc", "row_means", "global_mean"],
         meta_fields=["n", "block"])
@dataclasses.dataclass
class CondensedCenteredGramOperator:
    """The same centered-Gram operator, backed by the CONDENSED distances.

    ``repro.dist`` produces distances tile-by-tile and accumulates the
    row/global means of E = −½ D∘D while doing so; this operator is what
    those artifacts plug into — the m = n(n−1)/2 condensed vector is the
    **only** large buffer (half a square's bytes, and never an n×n
    allocation), and each matvec row strip is gathered from it on the fly
    with closed-form triangle indexing:

        k(i, j) = i(2n − i − 1)/2 + (j − i − 1)   for i < j  (scipy layout)

    The strip gather is O(b·n) int arithmetic + one vectorized gather —
    the same formulation as ``condensed_to_square`` (XLA:CPU scalarizes
    the scatter inverse ~70x), but per-strip, so no n×n position map is
    ever built either. Index arithmetic is int32 — the peak intermediate
    ``lo·(2n − lo − 1)`` is < n², exact only for n ≤ 46340, and an
    overflow would CLAMP the wrapped gather indices into silently wrong
    distances — so construction refuses larger n outright (the x64-off
    container has no int64 escape hatch; out-of-core production is the
    ROADMAP path past this bound anyway).

    D is hollow by construction (the diagonal is identically 0), so
    ``trace`` needs no diagonal term: tr(F) = −n·m̄.
    """

    dc: jax.Array           # (m,) condensed distances — the ONLY big buffer
    row_means: jax.Array    # (n,)  row means of E = −½ D∘D
    global_mean: jax.Array  # ()    global mean of E
    n: int
    block: int = 256

    # the single shared int32-exact bound (kernels.permute_reduce
    # enforces the same constant); kept as a class attribute for callers
    # that introspect it
    _MAX_N = MAX_TRIANGLE_N

    def __post_init__(self):
        if self.n > self._MAX_N:
            raise ValueError(
                f"CondensedCenteredGramOperator supports n <= "
                f"{self._MAX_N} (int32 triangle indexing would overflow "
                f"and silently corrupt the gather); got n={self.n}")

    @classmethod
    def from_production(cls, prod: dict, *,
                        block: int = 256) -> "CondensedCenteredGramOperator":
        """Wrap a ``repro.dist.pairwise_condensed`` result — the means were
        already accumulated during the distance production, so this costs
        nothing."""
        return cls(prod["condensed"], prod["row_means"],
                   prod["global_mean"], prod["n"], block)

    @property
    def dtype(self):
        return self.dc.dtype

    def row_panel(self, i0: int, b: int) -> jax.Array:
        """Rows [i0, i0+b) of D gathered from the condensed vector."""
        if self.dc.shape[0] == 0:            # n <= 1: no off-diagonal pairs
            return jnp.zeros((b, self.n), dtype=self.dtype)
        r = jnp.arange(i0, i0 + b, dtype=jnp.int32)[:, None]
        c = jnp.arange(self.n, dtype=jnp.int32)[None, :]
        k = condensed_index(r, c, self.n)
        on_diag = r == c
        return jnp.where(on_diag, 0.0, self.dc[jnp.where(on_diag, 0, k)])

    # -- the operator interface (duck-typed with CenteredGramOperator) ------
    def matvec(self, x: jax.Array) -> jax.Array:
        """``F @ x`` with each D row strip gathered from condensed storage;
        peak extra memory is one (block, n) strip, never n²."""
        squeeze = x.ndim == 1
        if squeeze:
            x = x[:, None]
        colsum = jnp.sum(x, axis=0)                  # 1ᵀX   (k,)
        corr = self.global_mean * colsum - jnp.matmul(
            self.row_means, x, precision=HIGHEST)        # m·1ᵀX − rᵀX
        b = max(min(self.block, self.n), 1)
        parts = []
        for i0 in range(0, self.n, b):               # static row strips
            bi = min(b, self.n - i0)
            rows = self.row_panel(i0, bi)
            e_rows = -0.5 * rows * rows              # fused into the dot
            parts.append(jnp.matmul(e_rows, x, precision=HIGHEST)
                         - self.row_means[i0:i0 + bi, None] * colsum[None, :]
                         + corr[None, :])
        out = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
        return out[:, 0] if squeeze else out

    def trace(self) -> jax.Array:
        """Exact ``tr(F) = Σλ``: the condensed form is hollow by
        construction, so tr(E) = 0 and tr(F) = −n·m̄."""
        return -self.n * self.global_mean

    def to_square(self) -> jax.Array:
        """The full symmetric hollow D — only for callers that explicitly
        demand a square hoist (gram/ranks); defeats the point otherwise."""
        from repro.core.distance_matrix import condensed_to_square
        return condensed_to_square(self.dc, self.n)

    def materialize(self) -> jax.Array:
        """The full Gower-centered F (the eigh oracle path)."""
        from repro.core.centering import center_distance_matrix
        return center_distance_matrix(self.to_square())


# --------------------------------------------------------------------------
# Distributed matvec — the shard_map mesh layout of core.centering
# --------------------------------------------------------------------------
def centered_gram_matvec_distributed(d: jax.Array, x: jax.Array, mesh,
                                     row_axis: str = "data",
                                     col_axis: str = "model") -> jax.Array:
    """``F @ x`` over a 2-D block-sharded D, no n² tensor anywhere.

    Same mesh layout as ``center_distance_matrix_distributed``: each device
    holds an (n/Pr, n/Pc) block of D. Per call it forms its E block in
    VMEM/cache, contracts against its column slice of X, and one ``psum``
    over the column axis assembles the row strip of E@X; the centering
    corrections need only O(n)+O(k) collectives (row sums over the column
    axis, 1ᵀX and rᵀX over the row axis). The hoisted statistics are
    recomputed per matvec — each device's share is O(n²/P) flops on a block
    it is already streaming, which keeps the function self-contained and
    the interconnect traffic at O(n·k).
    """
    n = d.shape[0]

    def _local(d_blk, x_col, x_row):
        e = -0.5 * d_blk * d_blk
        part = jax.lax.psum(jnp.matmul(e, x_col, precision=HIGHEST),
                            axis_name=col_axis)           # (n/Pr, k)
        local_row_sums = jnp.sum(e, axis=1)
        rm = jax.lax.psum(local_row_sums, axis_name=col_axis) / n
        gm = jax.lax.psum(jnp.sum(local_row_sums),
                          axis_name=(row_axis, col_axis)) / (n * n)
        colsum = jax.lax.psum(jnp.sum(x_row, axis=0), axis_name=row_axis)
        rmx = jax.lax.psum(jnp.matmul(rm, x_row, precision=HIGHEST),
                           axis_name=row_axis)
        return part - rm[:, None] * colsum[None, :] \
            + (gm * colsum - rmx)[None, :]

    f = jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(row_axis, col_axis), P(col_axis, None), P(row_axis, None)),
        out_specs=P(row_axis, None),
    )
    return f(d, x, x)
