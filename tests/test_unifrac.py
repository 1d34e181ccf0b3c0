"""Unweighted UniFrac through the normal path: ``PhyloTree`` (arrays and
Newick), the device tree hoist, the ``unweighted_unifrac`` metric in the
tiled production, and ``Workspace.from_features(..., tree=)`` — each
against the eager float64 oracle ``repro.dist.unifrac_ref`` on seeded
trees of three shapes, plus a 4-tip tree worked by hand."""

import jax
import numpy as np
import pytest

from repro.api import Workspace
from repro.core import DistanceMatrix
from repro.dist import PhyloTree, pairwise_distances, tree_hoist
from repro.dist.unifrac_ref import branch_presence, unweighted_unifrac_ref
from repro.obs.compile import sentinel

KEY = jax.random.PRNGKey(5)


# --------------------------------------------------------------------------
# seeded trees of three shapes, as (parent, length, tips)
# --------------------------------------------------------------------------
def _balanced(rng, tips):
    """A complete binary tree: node v's parent is (v − 1) // 2."""
    nodes = 2 * tips - 1
    parent = (np.arange(nodes) - 1) // 2
    parent[0] = -1
    return parent, rng.exponential(0.1, nodes), np.arange(tips - 1, nodes)


def _caterpillar(rng, tips):
    """Every internal node holds one tip and the next internal node."""
    parent = [-1]
    leaves = []
    spine = 0
    for i in range(tips - 1):
        parent += [spine, spine]
        leaves.append(len(parent) - 2)
        spine = len(parent) - 1
    leaves.append(spine)
    return (np.asarray(parent), rng.exponential(0.1, len(parent)),
            np.asarray(leaves))


def _zero_and_unary(rng, tips):
    """A random tree with unary nodes (chains of one child), a third of
    the branches of length 0, and two leaves no column names."""
    parent, leaves = [-1], [0]
    while len(leaves) < tips + 2:
        k = int(rng.integers(len(leaves)))
        v = leaves[k]
        if rng.random() < 0.25:                  # a unary node
            parent.append(v)
            leaves[k] = len(parent) - 1
            continue
        parent += [v, v]
        leaves[k] = len(parent) - 2
        leaves.append(len(parent) - 1)
    length = rng.exponential(0.1, len(parent))
    length[rng.random(len(parent)) < 1 / 3] = 0.0
    return np.asarray(parent), length, rng.permutation(leaves)[:tips]


SHAPES = {"balanced": _balanced, "caterpillar": _caterpillar,
          "zero_and_unary": _zero_and_unary}


def _counts(rng, n, d):
    """Sparse integer counts with an empty sample and a feature absent
    from every sample."""
    x = rng.poisson(2.0, (n, d)) * (rng.random((n, d)) < 0.3)
    x[1] = 0
    x[:, d // 2] = 0
    return x.astype(np.float32)


def _tree_and_table(shape, seed, tips=16, n=13):
    rng = np.random.default_rng(seed)
    parent, length, leaves = SHAPES[shape](rng, tips)
    perm = rng.permutation(tips)      # column order unrelated to the tree
    return (parent, length, leaves[perm]), _counts(rng, n, tips)


def _condensed(square):
    i, j = np.triu_indices(square.shape[0], k=1)
    return square[i, j]


# --------------------------------------------------------------------------
# the program against the oracle
# --------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_unifrac_matches_the_reference(shape, seed):
    arrays, x = _tree_and_table(shape, seed)
    want = unweighted_unifrac_ref(x, *arrays)
    tree = PhyloTree(*arrays)
    ws = Workspace.from_features(x, metric="unweighted_unifrac", tree=tree)
    got = np.asarray(ws.condensed())
    assert np.max(np.abs(got - _condensed(want))) <= 1e-5
    # the hoist's presence is the oracle's walk up the tree (a branch of
    # length 0 stores 0)
    presence = branch_presence(x, arrays[0], arrays[2])[:, tree.branches]
    emb = np.asarray(tree_hoist(x, tree))
    np.testing.assert_array_equal(emb > 0, presence & (tree.branch_length
                                                       > 0))
    sq = np.asarray(pairwise_distances(x, "unweighted_unifrac", tree=tree,
                                       block=8, feature_block=4))
    np.testing.assert_allclose(sq, want, rtol=0, atol=1e-5)


def test_a_four_tip_tree_by_hand():
    """((A:1,B:2):3,(C:4,D:5):6); A alone against B alone leaves
    branches A and B unshared (1 + 2) of A, B and their parent (1 + 2 +
    3): 0.5. A against C shares nothing: 1. A against all four:
    (2 + 4 + 5 + 6) / 21. An empty sample against anything present: 1;
    two empty samples: 0 (0/0)."""
    tree = PhyloTree.from_newick("((A:1,B:2):3,(C:4,D:5):6);")
    x = np.array([[1, 0, 0, 0], [0, 7, 0, 0], [0, 0, 2, 0], [1, 1, 1, 1],
                  [0, 0, 0, 0], [0, 0, 0, 0]], np.float32)
    got = np.asarray(pairwise_distances(x, "unweighted_unifrac", tree=tree))
    assert got[0, 1] == pytest.approx(0.5)
    assert got[0, 2] == pytest.approx(1.0)
    assert got[0, 3] == pytest.approx(17 / 21)
    assert got[2, 3] == pytest.approx(11 / 21)
    assert got[3, 4] == pytest.approx(1.0)
    assert got[4, 5] == 0.0
    np.testing.assert_allclose(
        got, unweighted_unifrac_ref(x, tree.parent, tree.length, tree.tips),
        atol=1e-6)


def test_duplicate_and_empty_rows_on_the_four_tip_tree():
    """The product form on the condensed path: a sample against its
    duplicate is at most a rounding above 0 (never below), two empty
    samples are at 0 (0/0), an empty one against a present one at 1."""
    tree = PhyloTree.from_newick("((A:1,B:2):3,(C:4,D:5):6);")
    x = np.array([[1, 0, 3, 0], [2, 0, 1, 0], [0, 0, 0, 0], [0, 0, 0, 0],
                  [1, 1, 1, 1], [5, 2, 7, 1]], np.float32)
    got = np.asarray(pairwise_distances(x, "unweighted_unifrac", tree=tree,
                                        out="condensed", block=4))
    want = _condensed(unweighted_unifrac_ref(x, tree.parent, tree.length,
                                             tree.tips))
    pair = {(i, j): k for k, (i, j) in
            enumerate(zip(*np.triu_indices(x.shape[0], k=1)))}
    for dup in ((0, 1), (4, 5)):
        assert 0.0 <= got[pair[dup]] <= 1e-6
    assert got[pair[2, 3]] == 0.0
    assert got[pair[0, 2]] == pytest.approx(1.0)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


@pytest.mark.parametrize("block", [8, 256])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_square_is_exactly_symmetric_and_hollow(shape, block):
    """The product form rounds (i, j) and (j, i) apart and leaves about
    1e-6 on the diagonal; the square form is symmetric and hollow all
    the same, and agrees with the oracle, in panels of 8 rows or in one
    panel (the default block)."""
    arrays, x = _tree_and_table(shape, 11, tips=20, n=21)
    tree = PhyloTree(*arrays)
    sq = np.asarray(pairwise_distances(x, "unweighted_unifrac", tree=tree,
                                       block=block))
    np.testing.assert_array_equal(sq, sq.T)
    np.testing.assert_array_equal(np.diag(sq), 0.0)
    np.testing.assert_allclose(sq, unweighted_unifrac_ref(x, *arrays),
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_pallas_panels_match_the_reference(shape):
    """The product path in panels of 8 rows, the last one padded (n=19),
    asked for feature chunks of 5 that a product metric does not take:
    each panel's one product over the whole embedding gives the
    oracle's distances."""
    arrays, x = _tree_and_table(shape, 13, tips=24, n=19)
    want = _condensed(unweighted_unifrac_ref(x, *arrays))
    got = np.asarray(pairwise_distances(
        x, "unweighted_unifrac", tree=PhyloTree(*arrays), out="condensed",
        block=8, feature_block=5))
    assert np.max(np.abs(got - want)) <= 1e-5


@pytest.mark.parametrize("method", ["mantel", "pcoa", "permanova"])
def test_analyses_match_a_workspace_on_the_reference_square(method):
    arrays, x = _tree_and_table("zero_and_unary", 7, tips=24, n=30)
    rng = np.random.default_rng(8)
    y = x + rng.poisson(1.0, x.shape) * (x > 0)
    tree = PhyloTree(*arrays)
    ws = [Workspace.from_features(t, metric="unweighted_unifrac", tree=tree)
          for t in (x, y)]
    sq = [Workspace(unweighted_unifrac_ref(t, *arrays)) for t in (x, y)]
    if method == "pcoa":
        a, b = ws[0].pcoa(dimensions=4), sq[0].pcoa(dimensions=4)
        np.testing.assert_allclose(np.asarray(a.eigenvalues),
                                   np.asarray(b.eigenvalues),
                                   rtol=1e-3, atol=1e-5)
        return
    if method == "mantel":
        a = ws[0].mantel(ws[1], permutations=99, key=KEY)
        b = sq[0].mantel(sq[1], permutations=99, key=KEY)
    else:
        groups = np.arange(30) % 3
        a = ws[0].permanova(groups, permutations=99, key=KEY)
        b = sq[0].permanova(groups, permutations=99, key=KEY)
    np.testing.assert_allclose(a.statistic, b.statistic, rtol=1e-4)
    assert abs(a.p_value - b.p_value) <= 2.5 / 100   # same null, fp jitter


def test_refresh_keeps_the_tree_and_hoists_once_per_shape():
    arrays, x = _tree_and_table("balanced", 3)
    tree = PhyloTree(*arrays)
    ws = Workspace.from_features(x, metric="unweighted_unifrac", tree=tree)
    ws.condensed()
    before = sentinel.snapshot()
    x2 = x * 2.0 + (x == 0) * np.eye(*x.shape, dtype=np.float32)
    ws.refresh(features=x2)
    got = np.asarray(ws.condensed())
    want = unweighted_unifrac_ref(x2, *arrays)
    assert np.max(np.abs(got - _condensed(want))) <= 1e-5
    # one hoist program per (n, T, B): a new table of the same shape
    # traces nothing new
    assert "dist.tree_hoist" not in sentinel.since(before)
    with pytest.raises(ValueError, match="takes no tree"):
        ws.refresh(features=x, metric="braycurtis", tree=tree)
    ws.refresh(features=x, metric="braycurtis")       # the tree stays out
    assert ws._tree is None


# --------------------------------------------------------------------------
# refused inputs
# --------------------------------------------------------------------------
def _bad(case):
    (parent, length, tips), x = _tree_and_table("balanced", 4)
    parent, length, tips = parent.copy(), length.copy(), tips.copy()
    metric = "unweighted_unifrac"
    if case == "tips_not_d":
        x = x[:, :-1]
    elif case == "negative_length":
        length[3] = -0.1
    elif case == "nan_length":
        length[5] = np.nan
    elif case == "two_roots":
        parent[2] = -1
    elif case == "cycle":
        parent[1], parent[3] = 3, 1
    elif case == "tip_not_a_leaf":
        tips[0] = 0
    elif case == "tree_metric_without_tree":
        return lambda: Workspace.from_features(x, metric=metric)
    elif case == "tree_with_another_metric":
        metric = "braycurtis"
    return lambda: Workspace.from_features(
        x, metric=metric, tree=PhyloTree(parent, length, tips))


@pytest.mark.parametrize("case", [
    "tips_not_d", "negative_length", "nan_length", "two_roots", "cycle",
    "tip_not_a_leaf", "tree_metric_without_tree",
    "tree_with_another_metric"])
def test_refused(case):
    with pytest.raises(ValueError):
        _bad(case)()


def test_a_distance_matrix_takes_no_tree_and_a_tree_is_a_phylotree():
    tree = PhyloTree.from_newick("(A:1,B:1);")
    with pytest.raises(ValueError, match="feature table"):
        Workspace(DistanceMatrix(np.zeros((2, 2), np.float32)), tree=tree)
    with pytest.raises(TypeError, match="PhyloTree"):
        Workspace.from_features(np.ones((3, 2), np.float32),
                                metric="unweighted_unifrac",
                                tree=(tree.parent, tree.length, tree.tips))


# --------------------------------------------------------------------------
# Newick
# --------------------------------------------------------------------------
@pytest.mark.parametrize("text, features, parent, length, tips", [
    ("(A:1,B:2);", None, [-1, 0, 0], [0, 1, 2], [1, 2]),
    ("((A:1,B:2)ab:3,C:4)root:0;", ["C", "A", "B"],
     [-1, 0, 1, 1, 0], [0, 3, 1, 2, 4], [4, 2, 3]),
    ("  ( 'a b':0.5 , [a comment] c:1e-1 ) ;\n", ["c", "a b"],
     [-1, 0, 0], [0, 0.5, 0.1], [2, 1]),
    ("((A:1):2,B:3);", None, [-1, 0, 1, 0], [0, 2, 1, 3], [2, 3]),
])
def test_newick_reads_short_trees(text, features, parent, length, tips):
    t = PhyloTree.from_newick(text, features)
    np.testing.assert_array_equal(t.parent, parent)
    np.testing.assert_allclose(t.length, length)
    np.testing.assert_array_equal(t.tips, tips)


@pytest.mark.parametrize("text, features", [
    ("(A:1,B:2)", None),             # no ';'
    ("((A:1,B:2);", None),           # unbalanced
    ("(A:1,B);", None),              # a branch without a length
    ("(A:1,B:x);", None),            # a length that is no number
    ("(A:1,A:2);", ["A"]),           # two leaves of one label
    ("(A:1,B:2);", ["A", "C"]),      # a feature that is no leaf
    ("(A:-1,B:2);", None),           # negative
])
def test_newick_refuses(text, features):
    with pytest.raises(ValueError):
        PhyloTree.from_newick(text, features)


def test_newick_reads_a_deep_caterpillar():
    depth = 5000
    text = "(" * depth + "A:1" + "".join(f",T{i}:1):1" for i in
                                          range(depth)) + ";"
    t = PhyloTree.from_newick(text)
    assert t.num_tips == depth + 1 and t.num_branches == 2 * depth
