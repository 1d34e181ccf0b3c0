"""Phylogenetic trees made from the seed, for the cells of a tree metric.

A tree is three arrays: ``parent`` (``-1`` at the root), ``length`` (of
the branch above each node; the root's is 0) and ``tips`` (the leaf of
each feature column of a table). ``yule`` makes one region's tree,
``graft`` the tree of another region of the gene over the same samples:
the first region's tree with the other region's own OTUs added, each as
the sister of a tip drawn at random. A shared OTU keeps its place: the
branch above the tip it joins is split in two at a uniform point, so
every path from the root keeps its length.
"""

from __future__ import annotations

import numpy as np


def yule(rng, tips: int, mean_length: float):
    """A rooted binary tree over ``tips`` leaves by the Yule (pure-birth)
    process: from one lineage, a leaf drawn uniformly splits in two until
    there are ``tips``. Branch lengths are exponential about
    ``mean_length``. The leaves are given to the columns in an order
    drawn from ``rng``, so a column's place says nothing of its tip's."""
    if tips < 1:
        raise ValueError(f"a tree needs a tip, got {tips}")
    parent = np.empty(2 * tips - 1, dtype=np.int64)
    parent[0] = -1
    leaves = [0]
    picks = rng.integers(0, np.arange(1, tips), dtype=np.int64)
    for i, k in enumerate(picks):
        v, a = leaves[k], 2 * i + 1
        parent[a] = parent[a + 1] = v
        leaves[k] = a
        leaves.append(a + 1)
    length = rng.exponential(mean_length, size=parent.size)
    length[0] = 0.0
    return parent, length, rng.permutation(np.asarray(leaves))


def graft(rng, tree, columns, mean_length: float):
    """The tree of a wider table over the same samples: ``columns[f]`` is
    the column of this table that holds the first table's feature f, and
    the columns ``columns[len(tree tips):]`` hold this table's own
    features, each grafted as the sister of a tip of ``tree`` drawn at
    random, on a branch exponential about ``mean_length``."""
    parent, length, tips = tree
    width, d = tips.size, columns.size
    new = d - width
    parent = np.concatenate([parent, np.empty(2 * new, np.int64)])
    length = np.concatenate([length, np.empty(2 * new)])
    out = np.empty(d, dtype=np.int64)
    out[columns[:width]] = tips
    joined = tips[rng.integers(0, width, size=new)]
    split = rng.random(new)
    grown = rng.exponential(mean_length, size=new)
    base = len(tree[0])
    for i, (t, s) in enumerate(zip(joined, split)):
        u, w = base + 2 * i, base + 2 * i + 1
        parent[u], length[u] = parent[t], length[t] * s
        parent[t], length[t] = u, length[t] * (1.0 - s)
        parent[w], length[w] = u, grown[i]
        out[columns[width + i]] = w
    return parent, length, out
