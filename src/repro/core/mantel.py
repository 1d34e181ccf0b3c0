"""Mantel test: paper §4.2, Algorithms 3, 4 & 5.

The Mantel test correlates two distance matrices; significance comes from a
Monte-Carlo null distribution over K row/column permutations (default 999).

* ``mantel_ref`` — Algorithms 3+4 verbatim: per permutation, materialize the
  permuted condensed form and call a black-box ``pearsonr`` (eager, multi-pass:
  subtract mean, norm, divide, dot — each a DRAM round-trip).
* ``mantel`` — Algorithm 5's two hoisting observations plus fusion, expressed
  as a ``repro.stats.engine.Statistic`` (this module is a thin client of the
  shared permutation engine; the same split powers PERMANOVA, ANOSIM and the
  partial Mantel test in ``repro.stats``):
    1. the second argument never changes ⇒ normalize ``y`` once;
    2. mean and norm are permutation-invariant ⇒ compute ``x̄``, ``‖x−x̄‖`` once.
  Centering is permutation-invariant too, so the hoist stores x − x̄ (in
  exact arithmetic Σŷ = 0 would make that unnecessary; in fp32 it is
  not). The draws then run in one of two layouts, the statistic's
  ``layout``, which ``draw_layout`` picks from what the session observes:
    - ``"condensed"`` (every backend but the TPU, and any n whose squares
      do not fit): the condensed form of the permuted matrix is an index
      transform of the condensed original,
          ``condensed(X_p)[k] = xc[tri(order[i_k], order[j_k])]``,
      so ``r_p = ⟨condensed(X_p) − x̄, ŷ_c⟩ / ‖x−x̄‖`` is one closed-form
      element gather + one fused multiply-reduce over the m = n(n−1)/2
      condensed entries, batched B at a time through
      ``kernels.permute_reduce`` (~m(1 + 3/B) floats of traffic per
      permutation; the accounting lives in BENCH_mantel.json). Nothing
      square is built. XLA:CPU vectorizes this gather;
    - ``"rows"`` (a TPU, where the squares plus one draw's working set
      fit in half the chip's memory): the hoist builds X̂ = x − x̄ and Ŷ
      = ŷ square (hollow), and each draw is ½·sum(X̂[o] ⊙ Ŷ[o⁻¹]ᵀ)
      through ``kernels.permute_reduce_rows``: two whole-row gathers, one
      transpose, one multiply-reduce. A TPU serializes the condensed
      element gather, one index at a time; contiguous rows it does not.
* ``mantel_distributed`` — permutations sharded over ('pod','data'), matrix
  columns over 'model': each device reduces its column block, one psum.
  (The engine's ``permutation_test_distributed`` shards only the permutation
  axis; this path additionally splits the matrix columns, so it stays
  specialized here.)
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distance_matrix import (DistanceMatrix, condensed_index,
                                        condensed_to_square, triangle_coords)
from repro.kernels.dispatch import HIGHEST
from repro.kernels.permute_reduce_ops import (hollow_square, permute_reduce,
                                              permute_reduce_rows)
from repro.stats import engine


# --------------------------------------------------------------------------
# Algorithm 4 — SciPy pearsonr (the black box the original code calls)
# --------------------------------------------------------------------------
def pearsonr_ref(x_flat: jax.Array, y_flat: jax.Array) -> jax.Array:
    """Eager multi-pass Pearson correlation, mirroring scipy.stats.pearsonr."""
    xm = x_flat - x_flat.mean()
    ym = y_flat - y_flat.mean()
    normxm = jnp.linalg.norm(xm)
    normym = jnp.linalg.norm(ym)
    xnorm = xm / normxm
    ynorm = ym / normym
    return jnp.dot(xnorm, ynorm, precision=HIGHEST)


# --------------------------------------------------------------------------
# Algorithm 3 — original mantel (black-box pearsonr per permutation)
# --------------------------------------------------------------------------
_permutation_orders = engine.permutation_orders    # owned by the engine now


def mantel_ref(x: DistanceMatrix, y: DistanceMatrix, permutations: int = 999,
               key=None, alternative: str = "two-sided"):
    """Original implementation: the permuted matrix is fully materialized and
    pearsonr re-derives mean/norm from scratch every iteration."""
    key = engine.as_key(key)
    x_flat = x.condensed_form()
    y_flat = y.condensed_form()
    orig_stat = pearsonr_ref(x_flat, y_flat)

    orders = _permutation_orders(key, permutations, len(x))
    permuted_stats = []
    for p in range(permutations):                      # eager python loop, like NumPy
        x_perm_flat = x.permute(np.asarray(orders[p]), condensed=True)
        permuted_stats.append(pearsonr_ref(x_perm_flat, y_flat))
    permuted_stats = jnp.stack(permuted_stats)
    return _finish(orig_stat, permuted_stats, permutations, alternative, len(x))


# --------------------------------------------------------------------------
# Algorithm 5 — hoisted + fused mantel, as an engine Statistic
# --------------------------------------------------------------------------
@jax.jit
def condensed_moments_vec(flat: jax.Array) -> dict:
    """``condensed_moments`` for distances already in condensed layout —
    the entry point for feature-backed sessions (``repro.dist`` produces
    condensed directly, so the square extraction is skipped)."""
    centered = flat - flat.mean()
    norm = jnp.linalg.norm(centered)
    return {"norm": norm, "hat": centered / norm}


@partial(jax.jit, static_argnames=("n",))
def condensed_moments(data: jax.Array, n: int) -> dict:
    """The O(m) permutation-invariant moments of ONE matrix, cacheable per
    session: centered-condensed norm (the x-side hoist) and the centered-
    normalized condensed vector. Every Mantel-family hoist is assembled
    from these — BOTH sides, since the condensed batch loop: a fixed side
    contributes its ``hat`` vector directly, so a Workspace computes the
    moments once per matrix and nothing square is ever built (the square
    ``hat_square`` form survives only for ``mantel_distributed``'s
    column-sharded split)."""
    iu = np.triu_indices(n, k=1)
    return condensed_moments_vec(data[iu])


def hat_square(moments: dict, n: int) -> jax.Array:
    """Square symmetric form (diag 0) of the centered-normalized vector.
    Since the condensed batch loop the host-path statistics never need
    it; the one remaining consumer is ``mantel_distributed``, whose
    'model'-axis split shards the square's columns."""
    return condensed_to_square(moments["hat"], n)


def _centered(xc: jax.Array) -> jax.Array:
    """x − x̄, the permuted side as the statistics gather it. In exact
    arithmetic Σŷ = 0 makes centering x unnecessary, but an fp32 ŷ sums
    to about m·ulp(ȳ)/‖y−ȳ‖, and x̄ multiplies that into every draw: at
    n=512 it shifted each partial-Mantel draw by 1.2e-4."""
    return xc - xc.mean()


def _as_condensed(mat: jax.Array, n: int) -> jax.Array:
    """Condensed view of a square matrix; condensed input passes through.
    The statistics accept both so legacy square-matrix callers keep
    working while sessions feed condensed storage directly."""
    if mat.ndim == 1:
        return mat
    return mat[np.triu_indices(n, k=1)]


LAYOUTS = ("condensed", "rows")


def draw_layout(n: int, batch_size: int, backend: str,
                hbm_bytes: Optional[float]) -> str:
    """The Mantel draws' layout for a session: ``"rows"`` on a TPU where
    the row layout's working set fits in half of the chip's memory
    (``hbm_bytes``; None where unknown), else ``"condensed"``. XLA:CPU
    keeps the condensed loop, where its element gather vectorizes; an n
    whose squares do not fit keeps it too. The working set, in 4-byte
    words: the two squares, one draw's two gathered squares and its
    transposed operand, and a tile's orders and their inverses."""
    rows_bytes = 4 * (5 * n * n + 2 * batch_size * n)
    if backend == "tpu" and hbm_bytes and rows_bytes <= hbm_bytes / 2:
        return "rows"
    return "condensed"


@partial(jax.tree_util.register_dataclass,
         data_fields=["x", "y", "pre"],
         meta_fields=["n", "chunk", "layout"])
@dataclasses.dataclass
class MantelStatistic:
    """Pearson r between permuted x and fixed y, hoisting split per §4.2.
    In the ``"condensed"`` layout (the default) every hoist and every
    per-permutation pass works on the m = n(n−1)/2 condensed entries; in
    the ``"rows"`` layout the hoist builds the two hollow squares and the
    draws gather their rows (module docstring; ``draw_layout`` chooses).

    ``x``/``y`` may be square (n, n) matrices or condensed (m,) vectors.
    ``pre`` optionally carries the session-level hoist
    (``{"normxm": ..., "ynorm": ...}`` with ``ynorm`` the CONDENSED
    centered-normalized y, assembled from two Workspaces' cached
    ``condensed_moments``) so repeated tests against one matrix skip the
    per-test normalization passes — and a fixed side never builds any
    square form at all."""

    x: jax.Array           # (n, n) square or (m,) condensed, permuted side
    y: Optional[jax.Array]  # same, held fixed; may be None when pre is given
    n: int
    pre: Optional[dict] = None
    chunk: Optional[int] = None  # condensed stream chunk (None: kernel default)
    layout: str = "condensed"    # the draws' layout, one of LAYOUTS

    def __post_init__(self):
        if self.layout not in LAYOUTS:
            raise ValueError(f"unknown Mantel layout {self.layout!r}; "
                             f"expected one of {LAYOUTS}")

    def hoist(self):
        # the permuted side's centered condensed view and the triangle
        # coordinate map (or, in the row layout, the two squares) are
        # permutation-invariant too — built once, outside the loop
        inv = {"xc": _centered(_as_condensed(self.x, self.n))}
        if self.pre is not None:
            inv.update(self.pre)
        else:
            inv["normxm"] = jnp.linalg.norm(inv["xc"])  # computed once
            y_flat = _as_condensed(self.y, self.n)
            ym = y_flat - y_flat.mean()
            inv["ynorm"] = ym / jnp.linalg.norm(ym)    # computed exactly once
        if self.layout == "rows":
            inv["xs"] = hollow_square(inv.pop("xc"), self.n)
            inv["ys"] = hollow_square(inv.pop("ynorm"), self.n)[None]
            return inv
        inv["ii"], inv["jj"] = triangle_coords(self.n)
        return inv

    def per_perm(self, inv, order):
        if self.layout == "rows":
            return self.per_batch(inv, order[None])[0]
        # one closed-form condensed gather + one fused multiply-reduce
        # (Σ_uptri == ½ Σ_full and Σŷ = 0, so the full-matrix 2/(2‖x−x̄‖)
        # scaling collapses to 1/‖x−x̄‖ on condensed entries)
        o = order.astype(jnp.int32)
        k = condensed_index(o[inv["ii"]], o[inv["jj"]], self.n)
        return jnp.dot(inv["xc"][k], inv["ynorm"],
                       precision=HIGHEST) / inv["normxm"]

    def per_batch(self, inv, orders):
        # the engine's primary path: all B reductions of one order tile
        # through the batched kernel — the ŷ/triangle streams are fetched
        # once per tile and reused across the whole batch
        if self.layout == "rows":
            return (permute_reduce_rows(inv["xs"], inv["ys"], orders)[0]
                    / inv["normxm"])
        stats = permute_reduce(inv["xc"], inv["ynorm"][None, :], orders,
                               inv["ii"], inv["jj"], chunk=self.chunk)
        return stats[0] / inv["normxm"]


def _finish(orig_stat, permuted_stats, permutations, alternative, n):
    """Legacy tuple-returning finisher; the counting lives in the engine."""
    r = engine.finish(orig_stat, permuted_stats, permutations, alternative, n)
    return r.statistic, r.p_value, n


def mantel(x: DistanceMatrix, y: DistanceMatrix, permutations: int = 999,
           key=None, alternative: str = "two-sided"):
    """Cache-optimized Mantel test (paper Algorithm 5). Same interface and
    semantics as ``mantel_ref``. Its condensed batch loop (the layout
    off the TPU, ``draw_layout``) moves ~11.0x less per-permutation
    traffic than the square-gather engine loop and ~16.4x less than the
    eager Algorithm-3 original
    (analytic fp32 bytes at n=2048, B=32, K=999 — the audited accounting
    is the tracked ``BENCH_mantel.json`` artifact, via
    ``benchmarks/run.py --suite mantel``).
    Thin wrapper over a one-shot ``api.Workspace`` (which is itself a
    client of ``repro.stats.engine.permutation_test``) — identical
    p-values per key; a session testing one matrix against several should
    hold its own Workspace so the normalization hoists are shared."""
    from repro.api.workspace import Workspace
    # validate=False: trust the DistanceMatrix as constructed, exactly like
    # the pre-session implementation that read x.data directly
    r = Workspace(x, validate=False).mantel(y, permutations=permutations, key=key,
                            alternative=alternative)
    return r.statistic, r.p_value, r.sample_size


# --------------------------------------------------------------------------
# Distributed mantel — permutations over ('pod','data'), columns over 'model'
# --------------------------------------------------------------------------
def mantel_null_distributed(x: DistanceMatrix, y: DistanceMatrix, mesh,
                            permutations: int = 1024,
                            key: Optional[jax.Array] = None,
                            perm_axes=("data",), col_axis: str = "model"):
    """``(observed, null)`` of the permutation-parallel Mantel test.

    Each device owns K/|perm_axes| permutations and the full matrix column
    block assigned to its 'model' coordinate; the per-permutation reduction
    is block-local followed by one scalar psum over 'model'. Permutation
    draws use a per-device fold_in so the global null distribution is
    identical regardless of mesh shape (elastic-safe): the draws of
    permutation-device ``d`` come from ``fold_in(key, d)``.
    """
    from jax.sharding import PartitionSpec as P

    key = engine.as_key(key)
    n = len(x)
    x_data, y_data = x.data, y.data

    # one hoist implementation for host and distributed paths — only the
    # column-sharded reduction below stays specialized; the shared engine
    # entry point jits hoist + observed together so the identity-order
    # gathers fuse away instead of materializing two full n×n copies
    stat = MantelStatistic(x_data, y_data, n, layout="condensed")
    inv, orig_stat = engine.hoist_and_observe(stat)
    normxm = inv["normxm"]
    # this path shards the MATRIX columns over 'model', so it is the one
    # remaining consumer of the square hat form — assembled here from the
    # condensed hoist, not inside the statistic
    y_full = hat_square({"hat": inv["ynorm"]}, n)

    n_perm_devices = int(np.prod([mesh.shape[a] for a in perm_axes]))
    if permutations % n_perm_devices:
        raise ValueError(f"permutations ({permutations}) must divide over {n_perm_devices} devices")
    per_dev = permutations // n_perm_devices

    def _local(x_local, y_cols, normxm_s):
        # x_local: full matrix (replicated over perm axes); y_cols: (n, n/Pc)
        dev = jax.lax.axis_index(perm_axes[0]) if len(perm_axes) == 1 else (
            jax.lax.axis_index(perm_axes[0]) * mesh.shape[perm_axes[1]]
            + jax.lax.axis_index(perm_axes[1]))
        k = jax.random.fold_in(key, dev)
        orders = _permutation_orders(k, per_dev, n)
        j = jax.lax.axis_index(col_axis)
        c = y_cols.shape[1]

        def one(order):
            col_order = jax.lax.dynamic_slice(order, (j * c,), (c,))
            xp = x_local[order][:, col_order]          # only our column block
            part = jnp.vdot(xp, y_cols, precision=HIGHEST)
            return jax.lax.psum(part, axis_name=col_axis) / (2.0 * normxm_s)

        return jax.lax.map(one, orders)

    f = jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(), P(None, col_axis), P()),
        out_specs=P(perm_axes[0] if len(perm_axes) == 1 else perm_axes),
    )
    # centered like the statistic's condensed x (the hollow diagonal meets
    # y_full's zero diagonal, so it adds nothing either way)
    x_centered = x_data - jnp.sum(x_data) / (n * (n - 1))
    return orig_stat, f(x_centered, y_full, normxm)


def mantel_distributed(x: DistanceMatrix, y: DistanceMatrix, mesh,
                       permutations: int = 1024,
                       key: Optional[jax.Array] = None,
                       alternative: str = "two-sided",
                       perm_axes=("data",), col_axis: str = "model"):
    """Permutation-parallel Mantel (``mantel_null_distributed``), finished
    like ``mantel``: ``(statistic, p_value, n)``."""
    orig_stat, permuted_stats = mantel_null_distributed(
        x, y, mesh, permutations, key, perm_axes=perm_axes,
        col_axis=col_axis)
    return _finish(orig_stat, permuted_stats, permutations, alternative,
                   len(x))
