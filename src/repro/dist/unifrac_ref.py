"""Unweighted UniFrac by its definition: the eager float64 oracle.

Lozupone & Knight (Appl Environ Microbiol 71:8228, 2005): the distance
between two samples is the branch length of the tree that leads to tips
of one sample only, over the branch length that leads to tips of either.
A branch is present in a sample when some tip below it is. Here presence
is found by walking parent pointers up from each present tip, marking
every branch passed, and the distance is

    Σ_b l_b·|I_xb − I_yb| / Σ_b l_b·max(I_xb, I_yb),

over every branch b (every node but the root), in float64 NumPy. It
shares nothing with the program's tree hoist (``dist.tree``), which
finds presence from intervals of a depth-first order instead.

Departures from scikit-bio's ``beta_diversity("unweighted_unifrac")``:

* two samples with no present branch (both empty, or present only at
  the root) are at distance 0 (0/0 → 0, the convention of
  ``dist.metrics``), where scikit-bio returns NaN;
* a feature is present where its count is above 0: the table is not
  checked for non-negative integers;
* the tree is not pruned to the features present in the table, which
  changes nothing: a branch with no present tip below it adds 0 to both
  sums.
"""

from __future__ import annotations

import numpy as np


def branch_presence(table, parent, tips) -> np.ndarray:
    """(n, nodes) bool: node v is present in sample x when some present
    tip lies below it, found by walking up from each present tip."""
    table = np.asarray(table)
    parent = np.asarray(parent)
    out = np.zeros((table.shape[0], parent.size), dtype=bool)
    for x, row in enumerate(table):
        for f in np.flatnonzero(row > 0):
            v = int(tips[f])
            while v != -1 and not out[x, v]:
                out[x, v] = True
                v = int(parent[v])
    return out


def unweighted_unifrac_ref(table, parent, length, tips) -> np.ndarray:
    """The (n, n) float64 unweighted UniFrac matrix of ``table`` (n, d),
    whose column j is the leaf ``tips[j]`` of the tree given by
    ``parent`` (``-1`` at the root) and ``length`` (of the branch above
    each node)."""
    parent = np.asarray(parent)
    branch = parent != -1
    present = branch_presence(table, parent, tips)[:, branch]
    ix, iy = present[:, None, :], present[None, :, :]
    l = np.asarray(length, dtype=np.float64)[branch]
    num = np.sum(l * (ix != iy), axis=-1)
    den = np.sum(l * (ix | iy), axis=-1)
    with np.errstate(invalid="ignore", divide="ignore"):
        return np.where(den > 0, num / den, 0.0)
