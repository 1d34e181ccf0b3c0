"""ANOSIM (Clarke 1993) on the hoisted-permutation engine.

R = (mean between-group rank − mean within-group rank) / (n(n−1)/4), over
the ranks of the condensed distances. The paper §4.2 split:

* **hoisted** (computed once): the *ranks* — the expensive O(m log m) sort
  happens exactly once, never per permutation — kept CONDENSED, plus the
  condensed within-group indicator of the ORIGINAL labels
  (``w[k] = [codes[i_k] == codes[j_k]]``), the total rank sum, and the
  within-pair count ``Σ_g n_g(n_g−1)/2`` (group sizes are
  permutation-invariant, so both denominators are too).
* **per permutation**: only the *within-group rank sum* changes — and
  relabelling the samples by ``order`` makes display pair (i, j) a
  within-pair iff the ORIGINAL pair (order[i], order[j]) is one, so

      w_sum(p) = Σ_k ranks[k] · w[tri(order[i_k], order[j_k])]

  is exactly the ``kernels.permute_reduce`` shape: the rank vector
  streams once per B-permutation tile while the indicator is gathered by
  closed-form triangle indexing. The square rank matrix the PR-1 loop
  multiplied per permutation (``Z_pᵀ Rk Z_p``) is gone from the hot path
  entirely — no n×n buffer survives anywhere in the test.

``anosim_ref`` mirrors scikit-bio's eager evaluation: per permutation it
rebuilds the within-pair boolean mask over all m = n(n−1)/2 pairs and
takes two masked means — several full passes over the condensed vector.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.stats import rankdata

from repro.core.distance_matrix import (DistanceMatrix, condensed_index,
                                        triangle_coords)
from repro.kernels.dispatch import HIGHEST
from repro.kernels.permute_reduce_ops import permute_reduce
from repro.stats import engine
from repro.stats.engine import PermutationTestResult


def _rank_average(v: jax.Array) -> jax.Array:
    """scipy ``rankdata(method="average")``, via one sort + two binary
    searches instead of ``jax.scipy.stats.rankdata``'s argsort path (~25%
    cheaper at 2M elements — this is the fused test's dominant fixed
    cost). Ranks are half-integers below 2²⁴, so the two agree bitwise."""
    sv = jnp.sort(v)
    lo = jnp.searchsorted(sv, v, side="left")
    hi = jnp.searchsorted(sv, v, side="right")
    return 0.5 * (lo + hi + 1).astype(v.dtype)


@partial(jax.jit, static_argnames=("n",))
def rank_transform_condensed(flat: jax.Array, n: int = 0) -> dict:
    """The rank hoist straight from a condensed vector — everything about
    the ranks that ANOSIM's per-permutation pass consumes, and nothing
    square: since the batched loop gathers the condensed within-indicator
    directly, the rank matrix is never materialized (``n`` is accepted
    for backward compatibility but no longer needed)."""
    ranks = _rank_average(flat)                      # ranked exactly once
    return {"ranks": ranks, "total_sum": jnp.sum(ranks)}


@partial(jax.jit, static_argnames=("n",))
def rank_transform(dm_data: jax.Array, n: int) -> dict:
    """The O(m log m) rank hoist from a square matrix, split out so a
    Workspace can cache it. Bitwise-identical whether computed here (once
    per session) or inside ``AnosimStatistic.hoist`` (once per test)."""
    iu = np.triu_indices(n, k=1)
    return rank_transform_condensed(dm_data[iu])


@partial(jax.tree_util.register_dataclass,
         data_fields=["dm", "grouping", "pre"],
         meta_fields=["n", "num_groups", "chunk"])
@dataclasses.dataclass
class AnosimStatistic:
    """Clarke's R with ranks hoisted out of the Monte-Carlo loop, on the
    condensed batch-fused path.

    ``dm`` may be a square (n, n) matrix, a condensed (m,) vector, or
    ``None`` when ``pre`` carries the session-level rank hoist (the
    ``rank_transform`` dict from a Workspace's ``HoistCache``) so
    back-to-back tests on one matrix sort the condensed distances once."""

    dm: Optional[jax.Array]   # (n, n) square / (m,) condensed / None w/ pre
    grouping: jax.Array       # (n,) int group codes in [0, num_groups)
    n: int
    num_groups: int
    pre: Optional[dict] = None   # optional pre-hoisted rank_transform dict
    chunk: Optional[int] = None  # condensed stream chunk (None: kernel default)

    def hoist(self):
        from repro.core.mantel import _as_condensed
        if self.pre is not None:
            rt = self.pre
        else:
            rt = rank_transform_condensed(_as_condensed(self.dm, self.n))
        ii, jj = triangle_coords(self.n)
        codes = self.grouping.astype(jnp.int32)
        # condensed within-indicator over the ORIGINAL labels: permuting
        # the samples only permutes which pair is looked up, so this is
        # the one gatherable hoist the whole null distribution needs
        within = (codes[ii] == codes[jj]).astype(rt["ranks"].dtype)
        sizes = jnp.zeros(self.num_groups,
                          dtype=rt["ranks"].dtype).at[codes].add(1.0)
        m = self.n * (self.n - 1) / 2.0
        within_count = jnp.sum(sizes * (sizes - 1)) / 2.0
        return {"ranks": rt["ranks"], "within": within, "ii": ii, "jj": jj,
                "total_sum": rt["total_sum"],
                "within_count": within_count,
                "between_count": m - within_count,
                "divisor": self.n * (self.n - 1) / 4.0}

    def _finish_r(self, inv, w_sum):
        r_w = w_sum / inv["within_count"]
        r_b = (inv["total_sum"] - w_sum) / inv["between_count"]
        return (r_b - r_w) / inv["divisor"]

    def per_perm(self, inv, order):
        o = order.astype(jnp.int32)
        k = condensed_index(o[inv["ii"]], o[inv["jj"]], self.n)
        w_sum = jnp.dot(inv["ranks"], inv["within"][k], precision=HIGHEST)
        return self._finish_r(inv, w_sum)

    def per_batch(self, inv, orders):
        w_sums = permute_reduce(inv["within"], inv["ranks"][None, :],
                                orders, inv["ii"], inv["jj"],
                                chunk=self.chunk)
        return self._finish_r(inv, w_sums[0])


def anosim(dm: DistanceMatrix, grouping, permutations: int = 999,
           key=None, batch_size: int = 32) -> PermutationTestResult:
    """Hoisted+fused ANOSIM; one-sided (greater), like scikit-bio.

    Thin wrapper over a one-shot ``api.Workspace`` — identical p-values
    per key; a session running several tests should hold its own
    Workspace so the rank hoist is shared. Batches of 32 permutations
    share each streamed pass over the hoisted condensed ranks."""
    from repro.api.workspace import Workspace
    # validate=False: trust the DistanceMatrix as constructed, exactly like
    # the pre-session implementation that read dm.data directly
    return Workspace(dm, validate=False).anosim(grouping, permutations=permutations,
                                key=key, batch_size=batch_size)


# --------------------------------------------------------------------------
# Oracle — scikit-bio's evaluation order, deliberately eager and multi-pass
# --------------------------------------------------------------------------
def anosim_ref(dm: DistanceMatrix, grouping, permutations: int = 999,
               key=None) -> PermutationTestResult:
    """Per permutation: rebuild the within mask over all pairs, then two
    masked means — each an eager full-vector pass."""
    key = engine.as_key(key)
    codes, num_groups = engine.encode_grouping(grouping)
    n = len(dm)
    if codes.size != n:
        raise ValueError("grouping length does not match distance matrix")
    iu = np.triu_indices(n, k=1)
    ranks = rankdata(dm.condensed_form())            # skbio also ranks once
    divisor = n * (n - 1) / 4.0

    def r_stat(order):
        g_p = codes[np.asarray(order)]
        within = jnp.asarray(g_p[iu[0]] == g_p[iu[1]])
        w_n = jnp.sum(within)
        r_w = jnp.sum(jnp.where(within, ranks, 0.0)) / w_n
        r_b = jnp.sum(jnp.where(within, 0.0, ranks)) / (ranks.size - w_n)
        return (r_b - r_w) / divisor

    observed = r_stat(np.arange(n))
    orders = np.asarray(engine.permutation_orders(key, permutations, n))
    permuted = jnp.asarray([r_stat(orders[p]) for p in range(permutations)])
    return engine.finish(observed, permuted, permutations, "greater", n)
