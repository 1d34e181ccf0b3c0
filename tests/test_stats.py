"""repro.stats subsystem tests: engine protocol, each fused statistic vs
its eager scikit-bio-style oracle (statistic AND p-value, same PRNG key),
the refactored core.mantel engine path, and the distributed engine."""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mantel, mantel_ref, random_distance_matrix
from repro.core.mantel import MantelStatistic, draw_layout
from repro.stats import (anosim, anosim_ref, partial_mantel,
                         partial_mantel_ref, permanova, permanova_ref,
                         permdisp, permdisp_ref, permutation_test,
                         permutation_test_distributed)
from repro.stats.engine import (_null_distribution, encode_grouping,
                                permutation_orders)
from repro.stats.permanova import PermanovaStatistic

KEY = jax.random.PRNGKey(7)


def _dm(seed, n=36):
    return random_distance_matrix(jax.random.PRNGKey(seed), n)


def _grouping(n=36, k=3):
    return np.array([i % k for i in range(n)])


# --------------------------------------------------------------------------
# engine: the refactored mantel path is pinned against the oracle
# --------------------------------------------------------------------------
@pytest.mark.parametrize("alternative", ["two-sided", "greater", "less"])
def test_mantel_engine_matches_ref_all_alternatives(alternative):
    """Same key ⇒ identical permutations ⇒ identical p-value, any tail."""
    x, y = _dm(0), _dm(1)
    s_opt, p_opt, n_opt = mantel(x, y, permutations=48, key=KEY,
                                 alternative=alternative)
    s_ref, p_ref, n_ref = mantel_ref(x, y, permutations=48, key=KEY,
                                     alternative=alternative)
    assert abs(s_opt - s_ref) < 1e-5
    assert abs(p_opt - p_ref) < 1e-9
    assert n_opt == n_ref == 36


def test_engine_runs_custom_statistic():
    """The protocol is pluggable: a toy statistic goes through unchanged."""

    @partial(jax.tree_util.register_dataclass,
             data_fields=["v"], meta_fields=["n"])
    @dataclasses.dataclass
    class FirstElement:
        v: jax.Array
        n: int

        def hoist(self):
            return {"v": self.v}

        def per_perm(self, inv, order):
            return inv["v"][order[0]]

    v = jnp.arange(10.0)
    r = permutation_test(FirstElement(v, 10), permutations=33, key=KEY)
    assert r.statistic == 0.0                      # identity order → v[0]
    assert 0.0 < r.p_value <= 1.0
    assert r.sample_size == 10 and r.permutations == 33


def test_engine_per_batch_single_trace_any_k():
    """Satellite acceptance: the per_batch path pads orders to FULL
    batch_size tiles (wrapping real permutations) and masks the tail, so
    one jit trace serves every K — the pre-change engine traced a second
    program whenever batch_size didn't divide K (e.g. the canonical
    999 % 32)."""
    traced_shapes = []

    @partial(jax.tree_util.register_dataclass,
             data_fields=["v"], meta_fields=["n"])
    @dataclasses.dataclass
    class Probe:
        v: jax.Array
        n: int

        def hoist(self):
            return {"v": self.v}

        def per_perm(self, inv, order):
            return inv["v"][order[0]]

        def per_batch(self, inv, orders):
            traced_shapes.append(tuple(orders.shape))   # records per TRACE
            return inv["v"][orders[:, 0]]

    r = permutation_test(Probe(jnp.arange(10.0), 10), permutations=999,
                         key=KEY, batch_size=32)
    assert traced_shapes == [(32, 10)]     # one trace, full tiles only
    assert r.permutations == 999 and 0.0 < r.p_value <= 1.0
    # batch_size > K still runs (one padded tile) without a second trace
    traced_shapes.clear()
    r2 = permutation_test(Probe(jnp.arange(10.0) + 1.0, 10),
                          permutations=5, key=KEY, batch_size=8)
    assert traced_shapes == [(8, 10)]
    assert r2.permutations == 5


def test_engine_results_invariant_to_batch_size():
    """The tile size is an execution knob, never a semantic one: any
    batch_size (dividing K or not) gives bitwise-identical statistics
    and p-values for the same key, on the batch-fused mantel path."""
    x, y = _dm(0), _dm(1)
    rs = [permutation_test(MantelStatistic(x.data, y.data, len(x)),
                           permutations=45, key=KEY, batch_size=bs)
          for bs in (1, 7, 32, 64)]
    for r in rs[1:]:
        assert r.statistic == rs[0].statistic
        assert r.p_value == rs[0].p_value


def test_mantel_rows_layout_matches_condensed():
    """The row layout is the same test: same key, same p-value, every
    draw within 1e-5 of the condensed loop's, through a padded tail tile
    (K=999 over B=32)."""
    x, y = _dm(0), _dm(1)
    stats = {layout: MantelStatistic(x.data, y.data, len(x), layout=layout)
             for layout in ("condensed", "rows")}
    draws = {k: np.asarray(_null_distribution(st, KEY, 999, 32)[1])
             for k, st in stats.items()}
    np.testing.assert_allclose(draws["rows"], draws["condensed"], rtol=0,
                               atol=1e-5)
    r = {k: permutation_test(st, permutations=999, key=KEY, batch_size=32)
         for k, st in stats.items()}
    assert r["rows"].p_value == r["condensed"].p_value
    assert abs(r["rows"].statistic - r["condensed"].statistic) < 1e-5
    with pytest.raises(ValueError, match="layout"):
        MantelStatistic(x.data, y.data, len(x), layout="square")


@pytest.mark.parametrize("n,backend,want", [
    (2898, "cpu", "condensed"),      # XLA:CPU vectorizes the element gather
    (2898, "tpu", "rows"),           # HMP16SData: the squares fit
    (46340, "tpu", "condensed"),     # two 8.6 GB squares do not
])
def test_draw_layout_rule(n, backend, want):
    assert draw_layout(n, 32, backend, 16e9) == want


def test_workspace_reports_draw_layout():
    """A session's Mantel statistic carries the layout its report names
    (the condensed one on this CPU backend)."""
    from repro.api.workspace import Workspace
    x, y = _dm(0), _dm(1)
    ws = Workspace(x)
    stat, _ = ws.statistic("mantel", other=y)
    assert ws.resolved_tiles()["draw_layout"] == stat.layout == draw_layout(
        len(x), 32, jax.default_backend(), None) == "condensed"
    assert ws.report().to_dict()["meta"]["tiles"]["draw_layout"] == \
        "condensed"


def test_engine_rejects_bad_alternative():
    x, y = _dm(0), _dm(1)
    with pytest.raises(ValueError):
        mantel(x, y, permutations=4, alternative="bogus")
    with pytest.raises(ValueError):
        permutation_test(MantelStatistic(x.data, y.data, len(x)),
                         permutations=4, alternative="bogus")


def test_encode_grouping():
    codes, k = encode_grouping(["a", "b", "a", "c", "b", "a"])
    assert k == 3
    assert codes.tolist() == [0, 1, 0, 2, 1, 0]
    with pytest.raises(ValueError):
        encode_grouping(["a", "a", "a"])           # one group
    with pytest.raises(ValueError):
        encode_grouping(["a", "b", "c"])           # all singletons


# --------------------------------------------------------------------------
# permanova
# --------------------------------------------------------------------------
def test_permanova_fused_matches_ref():
    dm, g = _dm(2), _grouping()
    got = permanova(dm, g, permutations=99, key=KEY)
    want = permanova_ref(dm, g, permutations=99, key=KEY)
    assert abs(got.statistic - want.statistic) < 1e-5
    assert abs(got.p_value - want.p_value) < 1e-9


def test_permanova_detects_group_structure():
    """Points drawn around well-separated group centroids ⇒ huge F, p→min."""
    key = jax.random.PRNGKey(3)
    n, k = 45, 3
    g = _grouping(n, k)
    centers = 25.0 * jax.random.normal(key, (k, 4))
    pts = centers[g] + jax.random.normal(jax.random.fold_in(key, 1), (n, 4))
    d = jnp.sqrt(jnp.maximum(
        jnp.sum((pts[:, None] - pts[None, :]) ** 2, -1), 0))
    d = 0.5 * (d + d.T)
    from repro.core import DistanceMatrix
    dm = DistanceMatrix(d - jnp.diag(jnp.diag(d)), _skip_validation=True)
    r = permanova(dm, g, permutations=99, key=KEY)
    assert r.statistic > 50.0
    assert r.p_value == pytest.approx(1 / 100)
    # and no structure ⇒ F near 1, p not extreme
    r0 = permanova(_dm(4, n), g, permutations=99, key=KEY)
    assert r0.p_value > 0.05


def test_permanova_string_labels_and_validation():
    dm = _dm(5)
    labels = ["ctl" if i % 3 else "trt" for i in range(36)]
    r = permanova(dm, labels, permutations=49, key=KEY)
    assert 0.0 < r.p_value <= 1.0
    with pytest.raises(ValueError):
        permanova(dm, _grouping(12), permutations=9)   # length mismatch


# --------------------------------------------------------------------------
# anosim
# --------------------------------------------------------------------------
def test_anosim_fused_matches_ref():
    dm, g = _dm(6), _grouping()
    got = anosim(dm, g, permutations=99, key=KEY)
    want = anosim_ref(dm, g, permutations=99, key=KEY)
    assert abs(got.statistic - want.statistic) < 1e-5
    assert abs(got.p_value - want.p_value) < 1e-9


def test_anosim_r_range_and_structure():
    """R ∈ [−1, 1]; separated groups ⇒ R → 1 with minimal p."""
    key = jax.random.PRNGKey(8)
    n, k = 40, 4
    g = _grouping(n, k)
    centers = 50.0 * jax.random.normal(key, (k, 3))
    pts = centers[g] + jax.random.normal(jax.random.fold_in(key, 1), (n, 3))
    d = jnp.sqrt(jnp.maximum(
        jnp.sum((pts[:, None] - pts[None, :]) ** 2, -1), 0))
    d = 0.5 * (d + d.T)
    from repro.core import DistanceMatrix
    dm = DistanceMatrix(d - jnp.diag(jnp.diag(d)), _skip_validation=True)
    r = anosim(dm, g, permutations=99, key=KEY)
    assert 0.9 < r.statistic <= 1.0
    assert r.p_value == pytest.approx(1 / 100)
    r0 = anosim(_dm(9, n), g, permutations=99, key=KEY)
    assert -1.0 <= r0.statistic <= 1.0


# --------------------------------------------------------------------------
# permdisp
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n,k,perms", [(32, 3, 99), (27, 3, 49)])
def test_permdisp_fused_matches_ref(n, k, perms):
    """Acceptance: identical keys ⇒ identical permutation orders ⇒
    identical p-values, fused (matrix-free PCoA coords) vs eager oracle."""
    dm, g = _dm(23, n), _grouping(n, k)
    got = permdisp(dm, g, permutations=perms, key=KEY)
    want = permdisp_ref(dm, g, permutations=perms, key=KEY)
    assert abs(got.statistic - want.statistic) < 1e-4 * max(
        abs(want.statistic), 1.0)
    assert abs(got.p_value - want.p_value) < 1e-9


def test_permdisp_detects_dispersion_difference():
    """Two groups around one centroid, radically different spreads ⇒ huge
    F and the minimal p; equal spreads ⇒ F near 1, p not extreme."""
    key = jax.random.PRNGKey(30)
    n = 40
    g = _grouping(n, 2)
    scales = jnp.where(jnp.asarray(g) == 0, 0.05, 5.0)[:, None]
    pts = scales * jax.random.normal(key, (n, 3))
    d = jnp.sqrt(jnp.maximum(
        jnp.sum((pts[:, None] - pts[None, :]) ** 2, -1), 0))
    d = 0.5 * (d + d.T)
    from repro.core import DistanceMatrix
    dm = DistanceMatrix(d - jnp.diag(jnp.diag(d)), _skip_validation=True)
    r = permdisp(dm, g, permutations=99, key=KEY)
    assert r.statistic > 10.0
    assert r.p_value == pytest.approx(1 / 100)
    r0 = permdisp(_dm(34, n), g, permutations=99, key=KEY)
    assert r0.p_value > 0.05


def test_permdisp_low_dimensional_and_eigh():
    """dimensions=k truncation and the eigh coordinate path both run and
    stay consistent with each other on low-rank (dim=8 < k) input."""
    dm, g = _dm(32), _grouping()
    a = permdisp(dm, g, permutations=49, key=KEY, dimensions=12)
    b = permdisp(dm, g, permutations=49, key=KEY, dimensions=12,
                 method="eigh")
    assert abs(a.statistic - b.statistic) < 1e-3 * max(abs(b.statistic), 1.0)
    assert abs(a.p_value - b.p_value) < 1e-9


def test_permdisp_validation():
    dm = _dm(33)
    with pytest.raises(ValueError):
        permdisp(dm, _grouping(12), permutations=9)    # length mismatch
    with pytest.raises(ValueError):
        permdisp(dm, ["a"] * 36, permutations=9)       # one group


# --------------------------------------------------------------------------
# partial mantel
# --------------------------------------------------------------------------
def test_partial_mantel_fused_matches_ref():
    x, y, z = _dm(10), _dm(11), _dm(12)
    got = partial_mantel(x, y, z, permutations=48, key=KEY)
    want = partial_mantel_ref(x, y, z, permutations=48, key=KEY)
    assert abs(got.statistic - want.statistic) < 1e-5
    assert abs(got.p_value - want.p_value) < 1e-9


def test_partial_mantel_rejects_collinear_control():
    """z == y makes the residualization 0/0 — must raise, not report the
    most significant p-value via NaN comparisons."""
    x, y = _dm(20), _dm(21)
    with pytest.raises(ValueError, match="collinear"):
        partial_mantel(x, y, y, permutations=9)


def test_partial_mantel_controls_for_confounder():
    """y == x ⇒ partial r stays ~1 whatever z; controlling for x itself
    (z == x) leaves no partial correlation to report (0/0), so it is
    refused, never reported as a spurious correlation against an
    independent y."""
    x, z = _dm(16), _dm(17)
    r_same = partial_mantel(x, x, z, permutations=32, key=KEY)
    assert r_same.statistic > 0.99
    y_indep = _dm(18)
    with pytest.raises(ValueError, match="x and z are .*collinear"):
        partial_mantel(x, y_indep, x, permutations=99, key=KEY)


# --------------------------------------------------------------------------
# distributed engine (1-device mesh on CPU: exercises the shard_map path)
# --------------------------------------------------------------------------
def test_engine_distributed_single_device_mesh():
    from jax.sharding import Mesh

    n = 32
    dm = _dm(19, n)
    codes, k = encode_grouping(_grouping(n, 4))
    stat = PermanovaStatistic(dm.data, jnp.asarray(codes), n, k)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
    r = permutation_test_distributed(stat, mesh, permutations=64, key=KEY,
                                     alternative="greater")
    r_host = permutation_test(stat, permutations=64, key=KEY,
                              alternative="greater")
    # same observed statistic; the null draws differ (per-device fold_in)
    assert abs(r.statistic - r_host.statistic) < 1e-5
    assert 0.0 < r.p_value <= 1.0
    assert r.permutations == 64
    with pytest.raises(ValueError):
        permutation_test_distributed(stat, mesh, permutations=64,
                                     alternative="bogus")


def test_permutation_orders_deterministic():
    a = permutation_orders(KEY, 5, 12)
    b = permutation_orders(KEY, 5, 12)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for row in np.asarray(a):
        assert sorted(row.tolist()) == list(range(12))
