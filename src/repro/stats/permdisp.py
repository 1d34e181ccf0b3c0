"""PERMDISP (Anderson 2006) on the hoisted-permutation engine.

Homogeneity-of-dispersions test: ordinate the distance matrix (PCoA),
measure each sample's distance to its group centroid in ordination space,
and compare those dispersions across groups with a one-way ANOVA F whose
null distribution comes from permuting the group labels.

The paper §4.2 split, with the ordination itself as the headline hoist:

* **hoisted** (computed once): the PCoA **coordinates** — produced by the
  matrix-free operator pipeline (``core.pcoa``), so the hoist never
  materializes the n×n *centered* matrix. Note the ordination cost scales
  with the requested dimensionality: the scikit-bio-parity default
  (``dimensions=None`` → all n−1 axes) runs the range-finder at full rank
  — (n, n) blocks and O(n²·n) flops — so at large n pass a small
  ``dimensions`` (≈10–50) to stay in the skinny-block regime the operator
  exists for. Also hoisted: the one-hot design ``Z`` and the group sizes.
* **per permutation**: centroids move with the labels, so each draw is
  ``C = Z_pᵀX / sizes`` (one (g, k) gather-matmul), the distances
  ``v_i = ‖x_i − C_{g(i)}‖`` (one fused O(n·k) pass), and the ANOVA F of
  ``v`` — O(n·g) more. Nothing per-permutation touches anything bigger
  than the hoisted (n, k) coordinates.

``permdisp_ref`` is the eager scikit-bio-style oracle: full ``eigh`` PCoA
in NumPy, then per permutation a Python loop over groups with black-box
``scipy.stats.f_oneway``. Identical keys ⇒ identical permutation orders ⇒
identical p-values.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distance_matrix import DistanceMatrix
from repro.kernels.dispatch import HIGHEST
from repro.stats import engine
from repro.stats.engine import PermutationTestResult


@partial(jax.tree_util.register_dataclass,
         data_fields=["coords", "grouping"], meta_fields=["n", "num_groups"])
@dataclasses.dataclass
class PermdispStatistic:
    """ANOVA F over distances-to-centroid, coordinates hoisted."""

    coords: jax.Array      # (n, k) PCoA coordinates (the expensive hoist)
    grouping: jax.Array    # (n,) int group codes in [0, num_groups)
    n: int
    num_groups: int

    def hoist(self):
        z = jax.nn.one_hot(self.grouping, self.num_groups,
                           dtype=self.coords.dtype)
        return {"x": self.coords, "z": z, "sizes": jnp.sum(z, axis=0)}

    def per_perm(self, inv, order):
        z = inv["z"][order]                          # O(n·g) label gather
        centroids = jnp.matmul(z.T, inv["x"],
                               precision=HIGHEST) / inv["sizes"][:, None]
        dev = inv["x"] - jnp.matmul(z, centroids,
                                    precision=HIGHEST)   # x_i − C_{g(i)}
        v = jnp.sqrt(jnp.maximum(jnp.sum(dev * dev, axis=1), 0.0))
        # one-way ANOVA F over the dispersions v
        group_means = jnp.matmul(z.T, v, precision=HIGHEST) / inv["sizes"]
        grand = jnp.mean(v)
        ss_between = jnp.sum(inv["sizes"] * (group_means - grand) ** 2)
        resid = v - jnp.matmul(z, group_means, precision=HIGHEST)
        ss_within = jnp.sum(resid * resid)
        dof_between = self.num_groups - 1
        dof_within = self.n - self.num_groups
        return (ss_between / dof_between) / (ss_within / dof_within)


def permdisp(dm: DistanceMatrix, grouping, permutations: int = 999,
             key=None,
             dimensions: Optional[int] = None, method: str = "fsvd",
             batch_size: int = 32) -> PermutationTestResult:
    """Hoisted+fused PERMDISP; one-sided (greater), like scikit-bio.

    Thin wrapper over a one-shot ``api.Workspace`` — identical p-values
    per key; a session should hold its own Workspace so the ordination
    hoist is shared with ``ws.pcoa()``. ``dimensions=None`` ordinates into
    the full n−1 axes (scikit-bio's behaviour — exact, but the hoist then
    runs the range-finder at full rank, O(n²·n)); a small ``dimensions``
    (≈10–50) trades a truncated dispersion measure for the skinny-block
    cost that makes large n tractable. ``method`` is forwarded to
    ``core.pcoa`` — the default "fsvd" runs matrix-free through
    ``CenteredGramOperator``, so no n² intermediate is built even once.
    ``key`` drives only the permutation orders (the fsvd range-finder uses
    pcoa's fixed internal key), so fused and ref agree
    permutation-for-permutation under one key.
    """
    # deferred: workspace imports core+stats; a top-level import here would
    # close that cycle during package init
    from repro.api.workspace import Workspace
    # validate=False: trust the DistanceMatrix as constructed, exactly like
    # the pre-session implementation that read dm.data directly
    return Workspace(dm, validate=False).permdisp(grouping, permutations=permutations,
                                  key=key, dimensions=dimensions,
                                  method=method, batch_size=batch_size)


# --------------------------------------------------------------------------
# Oracle — scikit-bio's evaluation order, deliberately eager and multi-pass
# --------------------------------------------------------------------------
def permdisp_ref(dm: DistanceMatrix, grouping, permutations: int = 999,
                 key: Optional[jax.Array] = None,
                 dimensions: Optional[int] = None) -> PermutationTestResult:
    """Full eager ``eigh`` PCoA, then per permutation a Python loop over
    groups (centroid, distances) and black-box ``scipy.stats.f_oneway``."""
    from scipy.stats import f_oneway

    from repro.core.centering import center_distance_matrix_ref
    from repro.core.pcoa import resolve_dimensions

    key = engine.as_key(key)
    codes, num_groups = engine.encode_grouping(grouping)
    n = len(dm)
    if codes.size != n:
        raise ValueError("grouping length does not match distance matrix")
    dims = resolve_dimensions(dimensions, n)

    centered = np.asarray(center_distance_matrix_ref(dm.data),
                          dtype=np.float64)
    evals, evecs = np.linalg.eigh(centered)
    order = np.argsort(-evals)[:dims]
    coords = evecs[:, order] * np.sqrt(np.maximum(evals[order], 0.0))

    def f_stat(perm):
        g_p = codes[np.asarray(perm)]
        v = np.empty(n)
        for g in range(num_groups):                  # one pass per group
            mask = g_p == g
            c = coords[mask].mean(axis=0)
            v[mask] = np.linalg.norm(coords[mask] - c, axis=1)
        return f_oneway(*(v[g_p == g] for g in range(num_groups))).statistic

    observed = f_stat(np.arange(n))
    orders = np.asarray(engine.permutation_orders(key, permutations, n))
    permuted = jnp.asarray([f_stat(orders[p]) for p in range(permutations)])
    return engine.finish(jnp.asarray(observed, dtype=permuted.dtype),
                         permuted, permutations, "greater", n)
