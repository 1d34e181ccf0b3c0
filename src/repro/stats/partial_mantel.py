"""Partial Mantel test (Smouse, Long & Sokal 1986) on the hoisted engine.

Correlates distance matrices x and y while controlling for a third matrix
z: the statistic is the first-order partial correlation

    r_xy·z = (r_xy − r_yz·r_xz) / √((1 − r_xz²)(1 − r_yz²))

under row/column permutations of x only. The paper §4.2 split is richer
here than for the plain Mantel test:

* **hoisted** (computed once): x̄ and ‖x−x̄‖; the centered-normalized ŷ
  and ẑ; ``r_yz`` (y and z are never permuted, so it is a constant of the
  null distribution!); and the *residualized* numerator vector
  ``ŷ_res = (ŷ − r_yz·ẑ)/√(1−r_yz²)`` — the regression of ŷ on ẑ is done
  exactly once, not per permutation. Every hoist is CONDENSED (m =
  n(n−1)/2): no square form of any operand is ever built.
* **per permutation**: ONE closed-form condensed gather of the permuted
  x, shared by both multiply-reduces — ``⟨x_p, ŷ_res⟩`` (the numerator,
  pre-residualized) and ``⟨x_p, ẑ⟩`` (= r_xz) — then a scalar finish
  ``num/√(1−r_xz²)``. Both inner products use Mantel's Σŷ=0 algebra (the
  mean term vanishes). The engine's batch path stacks (ŷ_res, ẑ) as two
  rows of one ``kernels.permute_reduce`` call, so the B-permutation tile
  streams each invariant once and gathers x once for the pair.

``partial_mantel_ref`` mirrors the classical eager evaluation (vegan /
scikit-bio style): per permutation it materializes the permuted condensed
x and calls black-box multi-pass ``pearsonr`` three times.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distance_matrix import (DistanceMatrix, condensed_index,
                                        triangle_coords)
from repro.kernels.dispatch import HIGHEST
from repro.kernels.permute_reduce_ops import permute_reduce
from repro.stats import engine
from repro.stats.engine import PermutationTestResult


@partial(jax.tree_util.register_dataclass,
         data_fields=["x", "y", "z", "pre"],
         meta_fields=["n", "chunk"])
@dataclasses.dataclass
class PartialMantelStatistic:
    """r_xy·z with ŷ residualized against ẑ once, outside the loop —
    condensed like ``MantelStatistic``'s default layout.

    ``x``/``y``/``z`` may be square (n, n) matrices or condensed (m,)
    vectors. ``pre`` optionally carries the session-level hoist
    (``{"normxm", "r_yz", "y_res", "z"}`` — all condensed — assembled
    from three Workspaces' cached ``condensed_moments`` by
    ``Workspace.partial_mantel``) so repeated tests reuse the
    normalization and residualization passes and the fixed sides never
    build a square form."""

    x: jax.Array           # permuted side
    y: Optional[jax.Array]  # held fixed; may be None when pre is given
    z: Optional[jax.Array]  # held fixed (the control); ditto
    n: int
    pre: Optional[dict] = None
    chunk: Optional[int] = None  # condensed stream chunk (None: kernel default)

    def hoist(self):
        from repro.core.mantel import _as_condensed, _centered
        inv = {"xc": _centered(_as_condensed(self.x, self.n))}
        if self.pre is not None:
            inv.update(self.pre)
        else:
            inv["normxm"] = jnp.linalg.norm(inv["xc"])

            def _hat(mat):
                flat = _as_condensed(mat, self.n)
                centered = flat - flat.mean()
                return centered / jnp.linalg.norm(centered)

            yhat, zhat = _hat(self.y), _hat(self.z)
            r_yz = jnp.dot(yhat, zhat, precision=HIGHEST)  # perm-invariant
            inv["r_yz"] = r_yz
            inv["y_res"] = (yhat - r_yz * zhat) / jnp.sqrt(1.0 - r_yz * r_yz)
            inv["z"] = zhat
        inv["ii"], inv["jj"] = triangle_coords(self.n)
        return inv

    def per_perm(self, inv, order):
        o = order.astype(jnp.int32)
        k = condensed_index(o[inv["ii"]], o[inv["jj"]], self.n)
        xg = inv["xc"][k]                            # ONE gather, two dots
        num = jnp.dot(xg, inv["y_res"], precision=HIGHEST) / inv["normxm"]
        r_xz = jnp.dot(xg, inv["z"], precision=HIGHEST) / inv["normxm"]
        return num / jnp.sqrt(1.0 - r_xz * r_xz)

    def per_batch(self, inv, orders):
        # (ŷ_res, ẑ) stacked: the tile's x gather is shared by both
        # reductions, and each invariant streams once per B permutations
        ys = jnp.stack([inv["y_res"], inv["z"]])
        stats = permute_reduce(inv["xc"], ys, orders, inv["ii"], inv["jj"],
                               chunk=self.chunk)
        num = stats[0] / inv["normxm"]
        r_xz = stats[1] / inv["normxm"]
        return num / jnp.sqrt(1.0 - r_xz * r_xz)


def partial_mantel(x: DistanceMatrix, y: DistanceMatrix, z: DistanceMatrix,
                   permutations: int = 999,
                   key=None,
                   alternative: str = "two-sided",
                   batch_size: int = 32) -> PermutationTestResult:
    """Hoisted+fused partial Mantel on the condensed batch loop. Thin
    wrapper over a one-shot ``api.Workspace`` — identical p-values per
    key; sessions hold their own Workspace to share the normalization
    hoists."""
    from repro.api.workspace import Workspace
    # validate=False: trust the DistanceMatrix as constructed, exactly like
    # the pre-session implementation that read x.data directly
    return Workspace(x, validate=False).partial_mantel(
        y, z, permutations=permutations, key=key, alternative=alternative,
        batch_size=batch_size)


# --------------------------------------------------------------------------
# Oracle — eager multi-pass evaluation, black-box pearsonr per permutation
# --------------------------------------------------------------------------
def partial_mantel_ref(x: DistanceMatrix, y: DistanceMatrix,
                       z: DistanceMatrix, permutations: int = 999,
                       key=None,
                       alternative: str = "two-sided"
                       ) -> PermutationTestResult:
    """Per permutation: materialize the permuted condensed x and call
    multi-pass ``pearsonr`` three times (r_xy, r_xz and — wastefully —
    r_yz, which never changes)."""
    # deferred: core.mantel is an engine client, so a top-level import here
    # would close the stats ↔ core.mantel cycle during package init
    from repro.core.mantel import pearsonr_ref
    key = engine.as_key(key)
    n = len(x)
    y_flat = y.condensed_form()
    z_flat = z.condensed_form()

    def r_partial(x_flat):
        r_xy = pearsonr_ref(x_flat, y_flat)
        r_xz = pearsonr_ref(x_flat, z_flat)
        r_yz = pearsonr_ref(y_flat, z_flat)          # recomputed every time
        return ((r_xy - r_yz * r_xz)
                / jnp.sqrt((1.0 - r_xz ** 2) * (1.0 - r_yz ** 2)))

    observed = r_partial(x.condensed_form())
    orders = engine.permutation_orders(key, permutations, n)
    permuted = jnp.stack([
        r_partial(x.permute(np.asarray(orders[p]), condensed=True))
        for p in range(permutations)])
    return engine.finish(observed, permuted, permutations, alternative, n)
