"""PERMANOVA (Anderson 2001) on the hoisted-permutation engine.

Pseudo-F for a one-way design over a distance matrix. The scikit-bio
implementation re-walks the condensed distance vector once per group per
permutation; here the paper §4.2 recipe applies cleanly:

* **hoisted** (computed once): the centered Gower matrix
  ``G = -½ J D∘D J`` via the fused ``core.centering`` pass, its trace
  (``SS_total`` — permutation-invariant by McArdle & Anderson 2001!), the
  one-hot group design ``Z`` and the group sizes.
* **per permutation**: permuting sample labels permutes the *rows of Z*,
  not the n×n matrix — an O(n·k) gather. Then
  ``SS_among = Σ_g (Z_pᵀ G Z_p)_gg / n_g`` is one gather-matmul whose only
  large operand is ``G``, read once per permutation batch (the engine
  vmaps the batch, so XLA streams each ``G`` tile against all B designs).
  ``F = (SS_among/(k−1)) / ((SS_total − SS_among)/(n−k))``.

``permanova_ref`` mirrors scikit-bio's eager multi-pass evaluation
(condensed d², boolean group masks, one pass per group per permutation)
and is the oracle for the tests and ``benchmarks/bench_stats.py``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.centering import center_distance_matrix
from repro.core.distance_matrix import DistanceMatrix
from repro.kernels.dispatch import HIGHEST
from repro.stats import engine
from repro.stats.engine import PermutationTestResult


@partial(jax.tree_util.register_dataclass,
         data_fields=["dm", "grouping", "pre"],
         meta_fields=["n", "num_groups"])
@dataclasses.dataclass
class PermanovaStatistic:
    """Pseudo-F with the permutation-invariant pieces hoisted.

    ``pre`` optionally carries a session-level hoist (``{"g": <centered
    Gower matrix>}`` from a Workspace's ``HoistCache``) so back-to-back
    tests on one matrix share the O(n²) centering pass instead of
    re-deriving it inside every ``hoist``.
    """

    dm: jax.Array          # (n, n) validated distance matrix
    grouping: jax.Array    # (n,) int group codes in [0, num_groups)
    n: int
    num_groups: int
    pre: Optional[dict] = None   # optional pre-hoisted {"g": ...}

    def hoist(self):
        g = self.pre["g"] if self.pre is not None else \
            center_distance_matrix(self.dm)          # fused: 2 reads, 2 writes
        z = jax.nn.one_hot(self.grouping, self.num_groups, dtype=g.dtype)
        sizes = jnp.sum(z, axis=0)
        return {"g": g, "z": z, "sizes": sizes, "ss_total": jnp.trace(g)}

    def per_perm(self, inv, order):
        z = inv["z"][order]                          # O(n·k) label gather
        s = jnp.sum(z * jnp.matmul(inv["g"], z, precision=HIGHEST),
                    axis=0)                          # (k,) quadratic forms
        ss_among = jnp.sum(s / inv["sizes"])
        ss_within = inv["ss_total"] - ss_among
        dof_among = self.num_groups - 1
        dof_within = self.n - self.num_groups
        return (ss_among / dof_among) / (ss_within / dof_within)


@partial(jax.tree_util.register_dataclass,
         data_fields=["op", "grouping"],
         meta_fields=["n", "num_groups"])
@dataclasses.dataclass
class PermanovaOperatorStatistic:
    """Pseudo-F with the Gower centering held as an OPERATOR, not a matrix.

    The quadratic forms PERMANOVA consumes — ``diag(Z_pᵀ G Z_p)`` — only
    ever touch G through products with the skinny (n, k) permuted design,
    and ``SS_total = tr(G)`` comes exactly from the operator's hoisted
    means (McArdle & Anderson 2001). So when the distances were produced
    by ``repro.dist`` (``Workspace.from_features``), the per-permutation
    pass is ``op.matvec(Z_p)`` against the **condensed** storage: the
    square n×n Gower matrix — the one hoist the materialized statistic
    cannot avoid — never exists, and each permutation batch streams
    (block, n) strips instead (roughly half the bytes of a square-G
    read, with the E-formation fused into the strip sweep).

    ``op`` is any centered-Gram operator pytree
    (``core.operators.CenteredGramOperator`` or the condensed-backed
    ``CondensedCenteredGramOperator``); its tiling metadata is static, so
    the jitted engine caches per (operator type, shape).
    """

    op: object             # centered-Gram operator pytree (G as an operator)
    grouping: jax.Array    # (n,) int group codes in [0, num_groups)
    n: int
    num_groups: int

    def hoist(self):
        z = jax.nn.one_hot(self.grouping, self.num_groups,
                           dtype=self.op.dtype)
        sizes = jnp.sum(z, axis=0)
        return {"z": z, "sizes": sizes, "ss_total": self.op.trace()}

    def per_perm(self, inv, order):
        z = inv["z"][order]                          # O(n·k) label gather
        s = jnp.sum(z * self.op.matvec(z), axis=0)   # (k,) quadratic forms
        ss_among = jnp.sum(s / inv["sizes"])
        ss_within = inv["ss_total"] - ss_among
        dof_among = self.num_groups - 1
        dof_within = self.n - self.num_groups
        return (ss_among / dof_among) / (ss_within / dof_within)


def permanova(dm: DistanceMatrix, grouping, permutations: int = 999,
              key=None, batch_size: int = 32) -> PermutationTestResult:
    """Hoisted+fused PERMANOVA; one-sided (greater), like scikit-bio.

    Thin wrapper over a one-shot ``api.Workspace`` — identical p-values
    per key; a session running several tests should hold its own
    Workspace so the centering hoist is shared. Default batch 32 (vs
    mantel's 8): the per-perm operand here is the (n, k) design, not an
    (n, n) gathered matrix, so a bigger batch amortizes the Gower-matrix
    read at negligible memory cost."""
    from repro.api.workspace import Workspace
    # validate=False: trust the DistanceMatrix as constructed, exactly like
    # the pre-session implementation that read dm.data directly
    return Workspace(dm, validate=False).permanova(grouping, permutations=permutations,
                                   key=key, batch_size=batch_size)


# --------------------------------------------------------------------------
# Oracle — scikit-bio's evaluation order, deliberately eager and multi-pass
# --------------------------------------------------------------------------
def permanova_ref(dm: DistanceMatrix, grouping, permutations: int = 999,
                  key=None) -> PermutationTestResult:
    """Per permutation: rebuild the pair masks and walk the condensed d²
    vector once per group — each step an eager full-vector pass."""
    key = engine.as_key(key)
    codes, num_groups = engine.encode_grouping(grouping)
    n = len(dm)
    if codes.size != n:
        raise ValueError("grouping length does not match distance matrix")
    d2 = dm.condensed_form() ** 2
    iu = np.triu_indices(n, k=1)
    sizes = np.bincount(codes, minlength=num_groups)
    ss_total = float(jnp.sum(d2)) / n
    dof_among = num_groups - 1
    dof_within = n - num_groups

    def f_stat(order):
        g_p = codes[np.asarray(order)]
        gi, gj = g_p[iu[0]], g_p[iu[1]]
        same = gi == gj
        ss_within = 0.0
        for g in range(num_groups):                  # one pass per group
            mask = same & (gi == g)
            ss_within += float(jnp.sum(jnp.where(mask, d2, 0.0))) / sizes[g]
        ss_among = ss_total - ss_within
        return (ss_among / dof_among) / (ss_within / dof_within)

    observed = f_stat(np.arange(n))
    orders = np.asarray(engine.permutation_orders(key, permutations, n))
    permuted = jnp.asarray([f_stat(orders[p]) for p in range(permutations)])
    return engine.finish(observed, permuted, permutations, "greater", n)
