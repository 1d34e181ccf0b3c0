"""repro.dist tests: every metric against a float64 oracle — scipy's
pdist, or for Jaccard its documented definition written out here —
(property-style sweeps over odd/non-tile-multiple shapes, zero rows with
the pinned 0/0 conventions), the production panel against
``pairwise_ref`` across awkward tile shapes, the fused hoist
accumulators against square-matrix recomputation, the condensed-backed operator against the
square operator, and the Workspace.from_features acceptance battery —
including the "no n×n square on the matrix-free path" guarantee, cache
refresh()/generation semantics, the eigh lower-k coords serving, and the
shared non-finite admission checks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy.spatial.distance import pdist, squareform

from repro.api import ExecConfig, Workspace
from repro.api.config import _KNOWN_METRICS
from repro.core import (CenteredGramOperator, CondensedCenteredGramOperator,
                        DistanceMatrix, condensed_moments_vec, pcoa)
from repro.dist import (METRICS, PhyloTree, condensed_size, get_metric,
                        takes_tree, pairwise_condensed, pairwise_distances)
from repro.dist.driver import _panel_stats
from repro.dist.pairwise_ref import pairwise_ref
from repro.dist.unifrac_ref import unweighted_unifrac_ref

KEY = jax.random.PRNGKey(7)
# the metrics scipy's pdist also computes; unweighted UniFrac reads a
# tree and has its own oracle (tests/test_unifrac.py)
PDIST_METRICS = sorted(m for m in METRICS if not takes_tree(METRICS[m]))


def _table(seed, n, d, nonneg=True):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d))
    if nonneg:
        x = np.abs(x)
    # sprinkle exact zeros so jaccard/canberra exercise their guards
    x[rng.random(size=x.shape) < 0.2] = 0.0
    return x.astype(np.float32)


def _jaccard64(x):
    """Condensed float64 Jaccard as ``repro.dist.metrics`` documents it:
    #(a≠b) / #(a≠0 ∨ b≠0), with 0/0 → 0."""
    x = np.asarray(x, dtype=np.float64)
    i, j = np.triu_indices(x.shape[0], k=1)
    a, b = x[i], x[j]
    num = np.sum(a != b, axis=1).astype(np.float64)
    den = np.sum((a != 0) | (b != 0), axis=1).astype(np.float64)
    return np.where(den > 0, num / np.where(den > 0, den, 1.0), 0.0)


def _pdist64(x, metric):
    """The float64 condensed oracle of an untreed metric."""
    # SciPy >= 1.17 reads non-boolean input to jaccard as presence/absence
    if metric == "jaccard":
        return _jaccard64(x)
    return pdist(np.asarray(x, dtype=np.float64), metric)


# --------------------------------------------------------------------------
# metrics vs the scipy oracle
# --------------------------------------------------------------------------
@pytest.mark.parametrize("metric", PDIST_METRICS)
@pytest.mark.parametrize("n,d", [(23, 17), (64, 5), (7, 33), (16, 16)])
def test_metric_matches_pdist(metric, n, d):
    """Acceptance: every metric ≤ 1e-5 off its float64 oracle on
    random fp32 tables, including odd / non-tile-multiple n and d."""
    x = _table(0, n, d)
    got = np.asarray(pairwise_distances(x, metric, out="condensed",
                                        block=16, feature_block=8))
    want = _pdist64(x, metric)
    assert got.shape == (condensed_size(n),)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("metric", PDIST_METRICS)
def test_zero_row_conventions(metric):
    """Pinned degenerate-pair conventions: two all-zero samples are at
    distance 0 for EVERY metric — including Bray–Curtis, where scipy
    returns NaN for the 0/0 denominator (documented in repro.dist.metrics)
    — and a zero row never produces non-finite distances."""
    x = _table(1, 12, 9)
    x[0] = 0.0
    x[5] = 0.0
    sq = np.asarray(pairwise_distances(x, metric, block=8, feature_block=4))
    assert sq[0, 5] == 0.0 and sq[5, 0] == 0.0
    assert np.all(np.isfinite(sq))
    # non-degenerate pairs still match the oracle
    want = squareform(_pdist64(x, metric))
    mask = np.ones_like(sq, dtype=bool)
    mask[0, 5] = mask[5, 0] = False        # the 0/0 pair (scipy: NaN)
    np.testing.assert_allclose(sq[mask], want[mask], rtol=1e-5, atol=1e-5)


def test_square_output_is_symmetric_hollow_and_validates():
    x = _table(2, 21, 6)
    sq = np.asarray(pairwise_distances(x, "braycurtis", block=8))
    assert np.array_equal(sq, sq.T)
    assert np.all(np.diag(sq) == 0.0)
    DistanceMatrix(sq)                     # fused validation passes


def test_get_metric_coercion_and_config_registry_sync():
    assert get_metric("euclidean") is METRICS["euclidean"]
    assert get_metric(METRICS["jaccard"]) is METRICS["jaccard"]
    with pytest.raises(ValueError, match="unknown metric"):
        get_metric("chebyshev")
    with pytest.raises(TypeError):
        get_metric(42)
    # ExecConfig's literal metric list (it imports nothing from repro)
    # must stay in sync with the live registry
    assert tuple(sorted(METRICS)) == tuple(sorted(_KNOWN_METRICS))
    with pytest.raises(ValueError, match="unknown metric"):
        ExecConfig(metric="chebyshev")
    with pytest.raises(ValueError):
        ExecConfig(feature_block=0)


# --------------------------------------------------------------------------
# the production panel and driver vs their oracles
# --------------------------------------------------------------------------
@pytest.mark.parametrize("metric", sorted(METRICS))
@pytest.mark.parametrize("n,d,block,fb", [(30, 11, 8, 4), (17, 7, 16, 16),
                                          (32, 12, 8, 5)])
def test_pairwise_kernel_matches_ref(metric, n, d, block, fb):
    """Acceptance: one (block, n) production panel and its fused row
    sums agree with the pure-jnp ``pairwise_ref`` across feature chunks
    that do not divide d. A tree metric reads a branch embedding: each
    column one branch length where the branch is present, else 0."""
    x = jnp.asarray(_table(3, n, d))
    if takes_tree(get_metric(metric)):
        x = jnp.where(x > 0, jnp.asarray(_table(9, 1, d)), 0.0)
    panel = x[:block]
    got, rs2 = _panel_stats(panel, x, metric=get_metric(metric),
                            feature_block=fb)
    want = np.asarray(pairwise_ref(panel, x, metric))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(rs2), np.sum(want * want, axis=1),
                               rtol=1e-5, atol=1e-5)


def _balanced_tree(tips):
    """A complete binary tree over ``tips`` leaves, as PhyloTree arrays:
    node v's parent is (v − 1) // 2, the leaves are the last nodes."""
    nodes = 2 * tips - 1
    parent = (np.arange(nodes) - 1) // 2
    parent[0] = -1
    length = np.random.default_rng(6).exponential(0.1, nodes)
    return parent, length, np.arange(tips - 1, nodes)


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_driver_matches_reference(metric):
    """The whole driver, panels that do not divide n and feature chunks
    that do not divide d, against each metric's float64 oracle: pdist,
    the Jaccard definition, or ``unifrac_ref`` on a tree."""
    x = _table(4, 27, 13)
    if takes_tree(get_metric(metric)):
        arrays = _balanced_tree(13)
        tree = PhyloTree(*arrays)
        want = squareform(unweighted_unifrac_ref(x, *arrays), checks=False)
    else:
        tree = None
        want = _pdist64(x, metric)
    got = np.asarray(pairwise_distances(x, metric, out="condensed",
                                        block=8, feature_block=4, tree=tree))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# fused hoist accumulators
# --------------------------------------------------------------------------
def test_fused_hoists_match_square_recomputation():
    """The driver's tile-accumulated operator means equal what
    CenteredGramOperator derives from the materialized square."""
    x = _table(5, 33, 9)
    prod = pairwise_condensed(x, "braycurtis", block=8, feature_block=4)
    sq = np.asarray(pairwise_distances(x, "braycurtis", block=8,
                                       feature_block=4)).astype(np.float64)
    rm = -0.5 * np.mean(sq * sq, axis=1)
    np.testing.assert_allclose(np.asarray(prod["row_means"]), rm,
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(float(prod["global_mean"]), rm.mean(),
                               rtol=1e-5, atol=1e-8)
    flat = squareform(sq, checks=False)
    np.testing.assert_allclose(np.asarray(prod["condensed"]), flat,
                               rtol=1e-5, atol=1e-6)


def test_condensed_operator_matches_square_operator():
    """matvec + trace parity: the condensed-backed operator is the same
    linear map as the square-backed one."""
    x = _table(6, 37, 8)
    prod = pairwise_condensed(x, "euclidean", block=16)
    op_c = CondensedCenteredGramOperator.from_production(prod, block=16)
    sq = pairwise_distances(x, "euclidean", block=16)
    op_s = CenteredGramOperator.from_distance(jnp.asarray(sq), block=16)
    v = jnp.asarray(_table(7, 37, 3, nonneg=False))
    np.testing.assert_allclose(np.asarray(op_c.matvec(v)),
                               np.asarray(op_s.matvec(v)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(op_c.trace()), float(op_s.trace()),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(op_c.to_square()),
                               np.asarray(sq), rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------------
# Workspace.from_features — the fused session
# --------------------------------------------------------------------------
def test_from_features_matrix_free_battery_never_builds_square():
    """Acceptance: from_features → pcoa → permanova (+ permdisp, anosim)
    completes without ever allocating an n×n square distance matrix on
    the matrix-free path."""
    x = _table(8, 40, 10)
    g = np.arange(40) % 4
    ws = Workspace.from_features(x, metric="braycurtis")
    ws.pcoa(dimensions=5)
    ws.permanova(g, permutations=49, key=KEY)
    ws.permdisp(g, permutations=49, key=KEY, dimensions=5)
    ws.anosim(g, permutations=49, key=KEY)
    assert "square" not in ws.cache
    assert ws._dm is None                   # the square was never touched
    # the production ran exactly once, and every later analysis reused it
    assert ws.cache.build_count("condensed") == 1
    assert ws.cache.build_count("dist_means") == 1
    assert ws.cache.build_count("operator") == 1
    # a second battery builds nothing new
    before = dict(ws.cache.misses)
    ws.pcoa(dimensions=5)
    ws.permanova(g, permutations=49, key=KEY)
    assert dict(ws.cache.misses) == before


def test_from_features_matches_square_workspace():
    """The fused session answers the same questions as a square-backed
    session over the identical distances (operator-form PERMANOVA and
    condensed-ranked ANOSIM vs their materialized twins)."""
    x = _table(9, 36, 8)
    g = np.arange(36) % 3
    ws = Workspace.from_features(x, metric="braycurtis")
    sq = pairwise_distances(x, "braycurtis")
    ws2 = Workspace(sq)

    a = ws.pcoa(dimensions=4)
    b = ws2.pcoa(dimensions=4)
    np.testing.assert_allclose(np.asarray(a.eigenvalues),
                               np.asarray(b.eigenvalues),
                               rtol=1e-3, atol=1e-5)
    pa = ws.permanova(g, permutations=99, key=KEY)
    pb = ws2.permanova(g, permutations=99, key=KEY)
    np.testing.assert_allclose(pa.statistic, pb.statistic, rtol=1e-4)
    assert abs(pa.p_value - pb.p_value) <= 2.5 / 100   # same null, fp jitter
    ra = ws.anosim(g, permutations=49, key=KEY)
    rb = ws2.anosim(g, permutations=49, key=KEY)
    assert ra.statistic == rb.statistic and ra.p_value == rb.p_value
    # the mantel family works too — fully condensed, no square demanded
    m = ws.mantel(ws2, permutations=49, key=KEY)
    assert m.statistic == pytest.approx(1.0, abs=1e-5)
    assert "square" not in ws.cache
    assert ws._dm is None


def test_mantel_all_sides_stay_square_free():
    """EVERY side of (partial) Mantel stays condensed: the permuted side's
    gathers go through closed-form triangle indexing (no square x), the
    fixed sides ride in as condensed hat vectors (no square y/z), and
    the moments are two-pass reductions of the condensed artifact."""
    x = _table(20, 20, 6)
    ws_x = Workspace.from_features(x, metric="euclidean")
    ws_y = Workspace.from_features(x + 0.1, metric="euclidean")
    ws_z = Workspace.from_features(_table(21, 20, 6), metric="euclidean")
    ws_x.mantel(ws_y, permutations=19, key=KEY)
    ws_x.partial_mantel(ws_y, ws_z, permutations=19, key=KEY)
    assert "square" not in ws_x.cache and ws_x._dm is None
    assert "square" not in ws_y.cache and ws_y._dm is None
    assert "square" not in ws_z.cache and ws_z._dm is None
    # moments() is the two-pass reduction of the cached condensed vector
    want = condensed_moments_vec(ws_y.condensed())
    assert float(ws_y.moments()["norm"]) == float(want["norm"])
    hat = np.asarray(ws_y.moments()["hat"])
    np.testing.assert_allclose(np.linalg.norm(hat), 1.0, rtol=1e-4)
    np.testing.assert_allclose(hat.sum(), 0.0, atol=1e-4)


def test_condensed_operator_rejects_overflow_n():
    """int32 triangle indexing is exact only to n = 46340 — larger n must
    refuse loudly instead of clamping wrapped gather indices."""
    with pytest.raises(ValueError, match="int32"):
        CondensedCenteredGramOperator(
            jnp.zeros((3,)), jnp.zeros((50000,)), jnp.float32(0.0), 50000)


def test_from_features_respects_config_metric_default():
    x = _table(11, 10, 5)
    ws = Workspace.from_features(x, config=ExecConfig(metric="euclidean"))
    assert ws._metric.name == "euclidean"
    got = np.asarray(ws.condensed())
    np.testing.assert_allclose(got, pdist(x.astype(np.float64)),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# refresh() — cache invalidation
# --------------------------------------------------------------------------
def test_refresh_yields_new_answers_and_rebuilds_once():
    """Satellite acceptance: after refresh(new_dm) the session returns the
    NEW matrix's answers and re-runs each hoist exactly once."""
    x1, x2 = _table(12, 24, 6), _table(13, 24, 6)
    sq1 = pairwise_distances(x1, "euclidean")
    sq2 = pairwise_distances(x2, "euclidean")
    g = np.arange(24) % 3

    ws = Workspace(sq1)
    old = ws.permanova(g, permutations=49, key=KEY)
    ws.pcoa(dimensions=4)
    assert ws.cache.build_count("gram") == 1

    ws.refresh(sq2)
    assert ws.generation == 1
    assert len(ws.cache) == 0               # every hoist dropped
    new = ws.permanova(g, permutations=49, key=KEY)
    ref = Workspace(sq2).permanova(g, permutations=49, key=KEY)
    assert new.statistic == ref.statistic and new.p_value == ref.p_value
    assert new.statistic != old.statistic
    assert ws.cache.build_count("gram") == 1      # re-ran exactly once
    ws.permanova(g, permutations=49, key=KEY)
    assert ws.cache.build_count("gram") == 1      # ...and then cached


def test_refresh_feature_backed_and_noarg():
    x = _table(14, 18, 5)
    ws = Workspace.from_features(x, metric="braycurtis")
    r0 = ws.pcoa(dimensions=3)
    ws.dm                                        # force the lazy square
    assert "square" in ws.cache

    ws.refresh()                                  # no-arg: caches only
    assert ws.generation == 1 and len(ws.cache) == 0
    assert ws._dm is None                         # derived square dropped
    r1 = ws.pcoa(dimensions=3)
    np.testing.assert_array_equal(np.asarray(r0.eigenvalues),
                                  np.asarray(r1.eigenvalues))
    assert ws.cache.build_count("condensed") == 1

    ws.refresh(features=x * 3.0)                  # new table, same metric
    assert ws.generation == 2 and ws._metric.name == "braycurtis"
    r2 = ws.pcoa(dimensions=3)
    assert ws.cache.build_count("condensed") == 1
    ref = Workspace.from_features(x * 3.0, metric="braycurtis").pcoa(
        dimensions=3)
    np.testing.assert_array_equal(np.asarray(r2.eigenvalues),
                                  np.asarray(ref.eigenvalues))
    with pytest.raises(ValueError, match="not both"):
        ws.refresh(np.eye(3) * 0.0, features=x)


# --------------------------------------------------------------------------
# coords cache: lower-k served from a higher-k eigh solution
# --------------------------------------------------------------------------
def test_eigh_lower_k_served_from_higher_k():
    """Satellite acceptance: a lower-k eigh request slices the cached
    higher-k solution — a HIT on the higher-k entry, no new solve."""
    dm = pairwise_distances(_table(15, 30, 6), "euclidean")
    ws = Workspace(dm)
    full = ws.pcoa(dimensions=8, method="eigh")
    assert ws.cache.build_count("gram") == 1
    hits_before = ws.cache.hits[("coords", 8, "eigh", None)]

    low = ws.pcoa(dimensions=3, method="eigh")
    assert ws.cache.hits[("coords", 8, "eigh", None)] == hits_before + 1
    assert ws.cache.build_count("gram") == 1      # no re-centering either
    np.testing.assert_array_equal(np.asarray(low.coordinates),
                                  np.asarray(full.coordinates[:, :3]))
    np.testing.assert_array_equal(np.asarray(low.eigenvalues),
                                  np.asarray(full.eigenvalues[:3]))
    np.testing.assert_array_equal(
        np.asarray(low.proportion_explained),
        np.asarray(full.proportion_explained[:3]))
    # and it matches a direct lower-k solve bitwise
    direct = Workspace(dm).pcoa(dimensions=3, method="eigh")
    np.testing.assert_array_equal(np.asarray(low.coordinates),
                                  np.asarray(direct.coordinates))

    # repeats hit the lower-k entry itself
    ws.pcoa(dimensions=3, method="eigh")
    assert ws.cache.counts(("coords", 3, "eigh", None))[0] >= 1
    # fsvd must NOT be sliced (sketch width is k-dependent)
    ws.pcoa(dimensions=6)
    before = dict(ws.cache.misses)
    ws.pcoa(dimensions=2)
    assert dict(ws.cache.misses) != before        # a genuine new solve


# --------------------------------------------------------------------------
# non-finite rejection — the shared admission check
# --------------------------------------------------------------------------
def test_workspace_rejects_non_finite():
    bad = np.asarray(pairwise_distances(_table(16, 12, 5),
                                        "euclidean")).copy()
    bad[2, 7] = np.nan
    bad[7, 2] = np.nan
    with pytest.raises(ValueError, match="non-finite"):
        Workspace(bad)
    with pytest.raises(ValueError, match="non-finite"):
        Workspace(bad, validate=False)     # the opt-out doesn't skip it
    with pytest.raises(ValueError, match="non-finite"):
        Workspace(DistanceMatrix(bad, _skip_validation=True))


def test_pcoa_rejects_non_finite():
    bad = np.zeros((8, 8), dtype=np.float32)
    bad[1, 3] = bad[3, 1] = np.inf
    with pytest.raises(ValueError, match="non-finite"):
        pcoa(DistanceMatrix(bad, _skip_validation=True), dimensions=3)


def test_from_features_rejects_non_finite_table():
    x = _table(17, 9, 4)
    x[4, 2] = np.nan
    with pytest.raises(ValueError, match="feature table"):
        Workspace.from_features(x)
    with pytest.raises(ValueError, match="feature table"):
        Workspace.from_features(_table(18, 9, 4)).refresh(features=x)


def test_operator_only_pcoa_paths():
    """dm=None is the fully matrix-free entry — and only that."""
    prod = pairwise_condensed(_table(19, 16, 5), "euclidean", block=8)
    op = CondensedCenteredGramOperator.from_production(prod, block=8)
    r = pcoa(None, dimensions=3, operator=op)
    assert r.coordinates.shape == (16, 3)
    with pytest.raises(ValueError, match="matrix-free"):
        pcoa(None, dimensions=3, method="eigh", operator=op)
    with pytest.raises(ValueError):
        pcoa(None, dimensions=3)
