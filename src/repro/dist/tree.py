"""Rooted phylogenetic trees for the tree metrics, and the device tree hoist.

A ``PhyloTree`` holds a rooted tree as flat arrays: the parent of every
node (``-1`` at the root), the length of the branch above every node,
and the node of each feature column of the table (its tip). It validates
them once and, on the host, orders the tree once: a depth-first walk puts
the tips in an order in which the tips below any branch are one interval
``[lo, hi)``.

``tree_hoist`` turns an (n, T) count table into the (n, B) branch
embedding E[x, b] = l_b · [some tip below b is present in x], one column
per branch (every node but the root; B = 2T − 2 for a binary tree). With
the tips in depth-first order, branch b is present in sample x exactly
when the running count of x's present tips rises across its interval,
C_x[hi_b] − C_x[lo_b] > 0: one cumulative sum and two column gathers,
whatever the tree's depth, exact because it counts tips, not reads. No
(T, B) incidence matrix is built. Unweighted UniFrac is then
Σ_b |E_xb − E_yb| / Σ_b max(E_xb, E_yb), which production computes from
one matrix product per panel, the shared length [E > 0]·Eᵀ
(``metrics.UnweightedUniFrac``).

``PhyloTree.from_newick`` reads the Newick text in which trees reach
users (QIIME 2, HMP16SData).
"""

from __future__ import annotations

import re
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.compile import note_run, note_trace
from repro.obs.trace import current_obs


class PhyloTree:
    """A rooted tree over the feature columns of a table.

    ``parent[v]`` is node v's parent (``-1`` for the one root),
    ``length[v]`` the length of the branch above v (the root's is not
    used), ``tips[j]`` the node of feature column j: a leaf, each at most
    once. Leaves that no column names are allowed (a reference tree wider
    than the table); they are never present. Raises ``ValueError`` on
    anything that is not such a tree: no root or several, a parent out of
    range, a cycle, a negative or non-finite branch length, a tip that is
    not a leaf or is named twice."""

    def __init__(self, parent, length, tips):
        parent = np.asarray(parent)
        length = np.asarray(length, dtype=np.float64)
        tips = np.asarray(tips)
        if parent.ndim != 1 or length.shape != parent.shape:
            raise ValueError(f"parent and length must be 1-D arrays of one "
                             f"length, got {parent.shape} and {length.shape}")
        if tips.ndim != 1:
            raise ValueError(f"tips must be 1-D, got shape {tips.shape}")
        for name, a in (("parent", parent), ("tips", tips)):
            if a.size and not np.issubdtype(a.dtype, np.integer):
                raise ValueError(f"{name} must hold integers, got {a.dtype}")
        parent = parent.astype(np.int64)
        tips = tips.astype(np.int64)
        nodes = parent.size
        roots = np.flatnonzero(parent == -1)
        if roots.size != 1:
            raise ValueError(f"a rooted tree has one root (parent -1); "
                             f"found {roots.size}")
        if np.any((parent < -1) | (parent >= nodes)):
            raise ValueError("a parent index is out of range")
        others = parent != -1
        if not np.all(np.isfinite(length[others])) or \
                np.any(length[others] < 0):
            raise ValueError("branch lengths must be finite and >= 0")
        if np.any((tips < 0) | (tips >= nodes)):
            raise ValueError("a tip index is out of range")
        if np.unique(tips).size != tips.size:
            raise ValueError("a node is the tip of two feature columns")
        children = np.bincount(parent[others], minlength=nodes)
        if np.any(children[tips] > 0):
            raise ValueError("every tip must be a leaf of the tree")
        self.parent, self.length, self.tips = parent, length, tips
        self.order, self.lo, self.hi = _depth_first(parent, tips,
                                                    int(roots[0]))
        self.branches = np.flatnonzero(others)
        self.branch_length = length[self.branches]

    @property
    def num_tips(self) -> int:
        """Feature columns the tree places: the table's width d."""
        return int(self.tips.size)

    @property
    def num_branches(self) -> int:
        """B: the embedding's width, one column per non-root node."""
        return int(self.branches.size)

    @classmethod
    def from_newick(cls, text: str,
                    features: Optional[Sequence[str]] = None) -> "PhyloTree":
        """A tree from Newick text. ``features`` names the table's columns
        in order, each the label of a leaf; without it the columns are the
        leaves in the order the text lists them. Every branch but the
        root's needs a length (``:0.1``); internal labels and ``[...]``
        comments are read past; ``'quoted labels'`` keep their spaces."""
        parent, length, label = _parse_newick(text)
        nodes = len(parent)
        leaves = np.setdiff1d(np.arange(nodes), [p for p in parent if p >= 0])
        missing = [v for v in range(nodes)
                   if parent[v] >= 0 and length[v] is None]
        if missing:
            raise ValueError(f"{len(missing)} branches have no length "
                             f"(first: the branch above node {missing[0]}, "
                             f"label {label[missing[0]]!r})")
        if features is None:
            tips = leaves
        else:
            at = {}
            for v in leaves:
                if label[v] in at:
                    raise ValueError(f"two leaves are labelled "
                                     f"{label[v]!r}")
                at[label[v]] = v
            absent = [f for f in features if f not in at]
            if absent:
                raise ValueError(f"{len(absent)} features are no leaf of "
                                 f"the tree (first: {absent[0]!r})")
            tips = [at[f] for f in features]
        return cls(np.asarray(parent),
                   np.asarray([0.0 if x is None else x for x in length]),
                   np.asarray(tips, dtype=np.int64))


def _depth_first(parent: np.ndarray, tips: np.ndarray, root: int):
    """The feature columns in depth-first order of their tips, and each
    node's interval ``[lo, hi)`` of that order: the tips below it. Walks
    the whole tree once, so a node that does not reach the root (a cycle)
    is refused here."""
    nodes = parent.size
    by_parent = np.argsort(parent, kind="stable")
    first = np.searchsorted(parent[by_parent], np.arange(nodes + 1)).tolist()
    kids = by_parent.tolist()
    column = np.full(nodes, -1, dtype=np.int64)
    column[tips] = np.arange(tips.size)
    column = column.tolist()
    lo, hi = [0] * nodes, [0] * nodes
    order, seen = [], 0
    stack = [(root, False)]
    while stack:
        v, leaving = stack.pop()
        if leaving:
            hi[v] = len(order)
            continue
        seen += 1
        lo[v] = len(order)
        if column[v] >= 0:
            order.append(column[v])
        stack.append((v, True))
        below = kids[first[v]:first[v + 1]]
        stack.extend((c, False) for c in reversed(below))
    if seen != nodes:
        raise ValueError(f"{nodes - seen} nodes do not reach the root "
                         f"(a cycle in the parent pointers)")
    return (np.asarray(order, dtype=np.int64), np.asarray(lo, np.int64),
            np.asarray(hi, np.int64))


_TOKEN = re.compile(r"\s*(?:(\[[^\]]*\])|('(?:[^']|'')*')|([(),:;])"
                    r"|([^\s()\[\]',:;]+))")


def _parse_newick(text: str):
    """(parent, length, label) lists of the tree in ``text``; node 0 is
    the root. Iterative, so a deep (caterpillar) tree is no recursion."""
    parent, length, label = [-1], [None], [None]

    def new(p):
        parent.append(p)
        length.append(None)
        label.append(None)
        return len(parent) - 1

    stack, node, after_colon = [], 0, False
    pos, end = 0, len(text)
    done = False
    while pos < end:
        m = _TOKEN.match(text, pos)
        if m is None or m.end() == pos:
            if text[pos:].strip() == "":
                break
            raise ValueError(f"newick: cannot read {text[pos:pos + 20]!r}")
        pos = m.end()
        comment, quoted, punct, word = m.groups()
        if comment is not None:
            continue
        if done:
            raise ValueError("newick: text after the closing ';'")
        if after_colon:
            try:
                length[node] = float(word)
            except (TypeError, ValueError):
                raise ValueError(f"newick: a branch length must be a "
                                 f"number, got {m.group(0).strip()!r}")
            after_colon = False
        elif punct == "(":
            stack.append(node)
            node = new(node)
        elif punct == ",":
            if not stack:
                raise ValueError("newick: ',' outside any '( )'")
            node = new(stack[-1])
        elif punct == ")":
            if not stack:
                raise ValueError("newick: unbalanced ')'")
            node = stack.pop()
        elif punct == ":":
            after_colon = True
        elif punct == ";":
            done = True
        else:
            label[node] = (quoted[1:-1].replace("''", "'")
                           if quoted is not None else word)
    if stack or after_colon or not done:
        raise ValueError("newick: the text ends before the tree does "
                         "(unbalanced '(' or no ';')")
    return parent, length, label


@jax.jit
def _tree_hoist(x, order, lo, hi, length):
    """(n, T) table → (n, B) branch embedding. Profiler scope
    ``dist.tree_hoist``; one program per (n, T, B)."""
    note_trace("dist.tree_hoist", (x.shape, lo.shape[0]), _tree_hoist,
               (x, order, lo, hi, length))
    with jax.named_scope("dist.tree_hoist"):
        present = (jnp.take(x, order, axis=1) > 0).astype(jnp.int32)
        running = jnp.pad(jnp.cumsum(present, axis=1), ((0, 0), (1, 0)))
        inside = (jnp.take(running, hi, axis=1)
                  > jnp.take(running, lo, axis=1))
        return jnp.where(inside, length[None, :], 0.0)


def tree_hoist(x, tree: PhyloTree) -> jax.Array:
    """The (n, B) float32 branch embedding of the (n, T) table ``x`` on
    ``tree`` (module docstring): a feature is present where its count is
    above 0. Host span ``dist.tree_hoist``."""
    x = jnp.asarray(x)
    if x.ndim != 2 or x.shape[1] != tree.num_tips:
        raise ValueError(f"the tree places {tree.num_tips} features; the "
                         f"table has shape {x.shape}")
    b = tree.branches
    with current_obs().span("dist.tree_hoist", phase="production",
                            n=int(x.shape[0]), d=int(x.shape[1]),
                            branches=tree.num_branches):
        note_run(_tree_hoist, (x.shape, int(b.size)))
        return _tree_hoist(x, jnp.asarray(tree.order, jnp.int32),
                           jnp.asarray(tree.lo[b], jnp.int32),
                           jnp.asarray(tree.hi[b], jnp.int32),
                           jnp.asarray(tree.branch_length, jnp.float32))
