"""Unweighted UniFrac in float64, for the output check of a tree-metric cell.

Like ``reference.py`` it imports nothing of the program: it starts from
the feature tables and the tree arrays of ``trees.py``. The distance
between two samples is the branch length leading to tips of one of them
only, over the branch length leading to tips of either (Lozupone &
Knight, Appl Environ Microbiol 71:8228, 2005); a branch is present in a
sample when some tip below it is, a tip when its count is above 0.

Presence comes from walking parent pointers up from every tip: the walk
gives each tip's branches, a sparse (tips, branches) incidence, and a
sample's present branches are those its present tips reach. For 0/1
presence |a − b| = a + b − 2ab and max(a, b) = a + b − ab, so with
L_x = Σ_b l_b·I_xb and S_xy = Σ_b l_b·I_xb·I_yb the distance is
(L_x + L_y − 2·S_xy) / (L_x + L_y − S_xy), 0/0 taken as 0. S sums over
the branches in two parts: those present in more than ``DENSE`` of the
samples (near the root) as dense float64 products in blocks of
``BLOCK``, so no (samples, branches) float64 array is held whole, and
the rest as one sparse product.

``Precision`` (``reference.py``) rounds the stored operands: the branch
lengths, which is the embedding the program stores (l_b times 0 or 1),
and the distances.
"""

from __future__ import annotations

import numpy as np

from benchmarks.chip.reference import FLOAT64, Precision

BLOCK = 4096          # branches per dense block of S
DENSE = 0.05          # the share of samples above which a branch is dense


def presence(table, parent, tips):
    """(n, branches) sparse presence, the branches being the non-root
    nodes in order."""
    from scipy.sparse import csr_matrix
    parent = np.asarray(parent)
    column = np.cumsum(parent != -1) - 1          # node → branch column
    rows, cols = [], []
    tip, node = np.arange(tips.size), np.asarray(tips)
    while node.size:
        up = parent[node]
        below = up != -1                          # node is not the root
        rows.append(tip[below])
        cols.append(column[node[below]])
        tip, node = tip[below], up[below]
    rows, cols = np.concatenate(rows), np.concatenate(cols)
    branches = int(np.sum(parent != -1))
    reach = csr_matrix((np.ones(rows.size), (rows, cols)),
                       shape=(tips.size, branches))
    present = csr_matrix(np.asarray(table) > 0, dtype=np.float64)
    return (present @ reach) > 0


def unweighted_unifrac(table, tree, prec: Precision = FLOAT64):
    """The (n, n) float64 matrix of ``table`` (n, d) on ``tree``
    (``parent``, ``length``, ``tips``)."""
    from scipy.sparse import diags
    parent, length, tips = tree
    on = presence(table, parent, tips).tocsc().astype(np.float64)
    l = prec(np.asarray(length, dtype=np.float64)[np.asarray(parent) != -1])
    n = on.shape[0]
    total = on @ l                                # L_x
    count = np.asarray(on.sum(axis=0)).ravel()
    dense = np.flatnonzero(count > DENSE * n)
    rare = np.flatnonzero(count <= DENSE * n)
    some = on[:, rare]
    shared = (some @ diags(l[rare]) @ some.T).toarray()
    for b0 in range(0, dense.size, BLOCK):
        at = dense[b0:b0 + BLOCK]
        block = on[:, at].toarray()
        shared += (block * l[at]) @ block.T
    both = total[:, None] + total[None, :]
    num, den = both - 2.0 * shared, both - shared
    with np.errstate(invalid="ignore", divide="ignore"):
        square = np.where(den > 0, num / den, 0.0)
    np.fill_diagonal(square, 0.0)
    return prec(square)
