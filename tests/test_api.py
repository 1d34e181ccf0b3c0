"""repro.api tests: Workspace-routed analyses vs the standalone free
functions (bitwise golden parity per key), HoistCache hit/miss accounting
("the O(n²) hoist ran once" as an assertion), ExecConfig validation and
threading, unified RNG coercion, and the pcoa dimensions regression."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExecConfig, HoistCache, Workspace
from repro.core import (DistanceMatrix, mantel, pcoa,
                        random_distance_matrix, resolve_dimensions)
from repro.stats import (anosim, as_key, partial_mantel, permanova,
                         permdisp)

KEY = jax.random.PRNGKey(7)
N = 36


def _dm(seed, n=N):
    return random_distance_matrix(jax.random.PRNGKey(seed), n)


def _grouping(n=N, k=3):
    return np.array([i % k for i in range(n)])


# --------------------------------------------------------------------------
# golden parity: Workspace-routed == standalone, bitwise, same key
# --------------------------------------------------------------------------
def test_workspace_matches_standalone_bitwise():
    """Acceptance: the session API changes how often D is read, never the
    answer — p-values, statistics and coordinates are bitwise identical
    to the free functions for the same key."""
    dm, dm2, dm3, g = _dm(0), _dm(1), _dm(2), _grouping()
    ws = Workspace(dm)

    w_pcoa = ws.pcoa(dimensions=5)
    w_perm = ws.permanova(g, permutations=49, key=KEY)
    w_disp = ws.permdisp(g, permutations=49, key=KEY, dimensions=5)
    w_anos = ws.anosim(g, permutations=49, key=KEY)
    w_mant = ws.mantel(dm2, permutations=49, key=KEY)
    w_pmant = ws.partial_mantel(dm2, dm3, permutations=49, key=KEY)

    s_pcoa = pcoa(dm, dimensions=5)
    s_perm = permanova(dm, g, permutations=49, key=KEY)
    s_disp = permdisp(dm, g, permutations=49, key=KEY, dimensions=5)
    s_anos = anosim(dm, g, permutations=49, key=KEY)
    s_mant = mantel(dm, dm2, permutations=49, key=KEY)
    s_pmant = partial_mantel(dm, dm2, dm3, permutations=49, key=KEY)

    np.testing.assert_array_equal(np.asarray(w_pcoa.coordinates),
                                  np.asarray(s_pcoa.coordinates))
    np.testing.assert_array_equal(np.asarray(w_pcoa.eigenvalues),
                                  np.asarray(s_pcoa.eigenvalues))
    for w, s in [(w_perm, s_perm), (w_disp, s_disp), (w_anos, s_anos),
                 (w_pmant, s_pmant)]:
        assert w.statistic == s.statistic
        assert w.p_value == s.p_value
    assert (w_mant.statistic, w_mant.p_value, w_mant.sample_size) == s_mant


def test_workspace_hoists_run_once():
    """Acceptance: pcoa + permanova + permdisp + anosim on one Workspace
    performs each O(n²) centering/rank hoist at most once (miss counters),
    and repeats are pure cache hits."""
    dm, g = _dm(3), _grouping()
    ws = Workspace(dm)
    ws.pcoa(dimensions=5)
    ws.permanova(g, permutations=19, key=KEY)
    ws.permdisp(g, permutations=19, key=KEY, dimensions=5)
    ws.anosim(g, permutations=19, key=KEY)

    for artifact in ("operator", "gram", "ranks"):
        assert ws.cache.build_count(artifact) <= 1, artifact
    assert ws.cache.build_count("coords") == 1      # permdisp reused pcoa's
    assert ws.cache.counts(("coords", 5, "fsvd",
                            tuple(np.asarray(jax.random.PRNGKey(42)))))[0] >= 1

    # a second round of the same analyses builds nothing new
    before = dict(ws.cache.misses)
    ws.permanova(g, permutations=19, key=KEY)
    ws.anosim(g, permutations=19, key=KEY)
    ws.pcoa(dimensions=5)
    assert dict(ws.cache.misses) == before
    assert ws.cache.hits["gram"] >= 1
    assert ws.cache.hits["ranks"] >= 1


def test_hoist_cache_counters():
    c = HoistCache()
    assert c.get("a", lambda: 41) == 41
    assert c.get("a", lambda: 99) == 41              # cached, not rebuilt
    assert c.counts("a") == (1, 1)
    assert c.build_count("a") == 1
    assert ("a" in c) and len(c) == 1
    c.get(("coords", 3), lambda: "x")
    c.get(("coords", 5), lambda: "y")
    assert c.build_count("coords") == 2


def test_workspace_mantel_shares_both_sides():
    """Both operands' moments come from their own session caches: testing
    x against two different y-sides reuses the x-side condensed moments
    (zero extra normalization passes), a shared y-Workspace is normalized
    once across sessions — and NO session builds any square artifact:
    the condensed batch loop needs neither a square hat form nor square
    distances."""
    x, y, z = Workspace(_dm(4)), Workspace(_dm(5)), Workspace(_dm(6))
    x.mantel(y, permutations=19, key=KEY)
    x.mantel(z, permutations=19, key=KEY)            # new y-side...
    x.partial_mantel(y, z, permutations=19, key=KEY)
    for ws in (x, y, z):
        assert ws.cache.build_count("moments") == 1  # ...same x-side hoist
        assert ws.cache.build_count("condensed") == 1
        assert ws.cache.build_count("square") == 0
        assert ws.cache.build_count("hat_full") == 0  # artifact retired
    # the x-side moments were HIT (reused) on the second and third tests
    assert x.cache.counts("moments")[0] >= 2


def test_workspace_mantel_family_square_free_on_features():
    """Satellite acceptance: a Mantel-family call on a feature-backed
    Workspace performs ZERO ``"square"`` cache builds — the whole family
    (and ANOSIM's ranks) runs off condensed storage."""
    k1, k2, k3 = (jax.random.PRNGKey(s) for s in (50, 51, 52))
    t = np.abs(np.asarray(jax.random.normal(k1, (30, 7))))
    ws = Workspace.from_features(t, metric="braycurtis")
    ws_y = Workspace.from_features(
        np.abs(np.asarray(jax.random.normal(k2, (30, 7)))))
    ws_z = Workspace.from_features(
        np.abs(np.asarray(jax.random.normal(k3, (30, 7)))))
    g = _grouping(30)
    ws.pcoa(dimensions=3)
    ws.permanova(g, permutations=19, key=KEY)
    ws.permdisp(g, permutations=19, key=KEY, dimensions=3)
    ws.anosim(g, permutations=19, key=KEY)
    ws.mantel(ws_y, permutations=19, key=KEY)
    ws.partial_mantel(ws_y, ws_z, permutations=19, key=KEY)
    for w in (ws, ws_y, ws_z):
        assert w.cache.build_count("square") == 0
        assert w._dm is None                    # never even wrapped one


# --------------------------------------------------------------------------
# ExecConfig
# --------------------------------------------------------------------------
def test_execconfig_validates():
    with pytest.raises(ValueError):
        ExecConfig(centering_impl="bogus")
    with pytest.raises(ValueError):
        ExecConfig(centering_impl="distributed")     # needs a mesh
    with pytest.raises(ValueError):
        ExecConfig(batch_size=0)
    cfg = ExecConfig(block=128).replace(batch_size=16)
    assert cfg.block == 128 and cfg.batch_size == 16
    assert cfg.resolve_batch_size(None, 32) == 16    # config beats default
    assert cfg.resolve_batch_size(4, 32) == 4        # explicit beats config
    # leaf-free pytree: hashable, jit-static-safe
    assert not jax.tree_util.tree_leaves(cfg)
    assert hash(cfg) == hash(ExecConfig(block=128, batch_size=16))


def test_workspace_canonicalizes_and_validates():
    raw = np.asarray(_dm(11).data, dtype=np.float64)
    ws = Workspace(raw)                              # raw array accepted
    assert ws.data.dtype == jnp.float32              # canonical fp32
    assert ws.dm._validated
    with pytest.raises(Exception):
        Workspace(raw + np.eye(N))                   # non-hollow rejected
    with pytest.raises(ValueError):
        Workspace(_dm(11)).mantel(_dm(12, 20))       # shape mismatch
    with pytest.raises(ValueError):
        Workspace(_dm(11)).permanova(_grouping(12))  # grouping mismatch


def test_workspace_validate_false_is_consistent():
    """validate=False admits the matrix once for the whole session — no
    later analysis revalidates (pcoa's internal copy used to re-run the
    check the caller explicitly opted out of)."""
    bad = np.array(_dm(20, 16).data).copy()
    bad[0, 1] += 0.5                                 # asymmetric on purpose
    ws = Workspace(bad, validate=False)
    assert ws.dm._validated                          # trusted once admitted
    ws.pcoa(dimensions=3)                            # copy() must not raise
    with pytest.raises(Exception):
        Workspace(bad)                               # default still rejects
    # a directly-constructed session validates an unvalidated
    # DistanceMatrix wrapper just like a raw array...
    bad_dm = DistanceMatrix(jnp.asarray(bad), validate=False)
    with pytest.raises(Exception):
        Workspace(bad_dm)
    assert Workspace(bad_dm, validate=False).dm._validated
    # ...but the legacy free functions trust it as constructed, exactly
    # like the pre-session implementations that read dm.data directly
    r = permanova(bad_dm, _grouping(16), permutations=9, key=KEY)
    assert 0.0 < r.p_value <= 1.0


def test_workspace_collinear_control_raises():
    x, y = _dm(13), _dm(14)
    with pytest.raises(ValueError, match="collinear"):
        Workspace(x).partial_mantel(y, y, permutations=9)


# --------------------------------------------------------------------------
# unified RNG handling
# --------------------------------------------------------------------------
def test_as_key_coercion_rule():
    np.testing.assert_array_equal(np.asarray(as_key(None, default=5)),
                                  np.asarray(jax.random.PRNGKey(5)))
    np.testing.assert_array_equal(np.asarray(as_key(7)),
                                  np.asarray(jax.random.PRNGKey(7)))
    np.testing.assert_array_equal(np.asarray(as_key(np.int64(7))),
                                  np.asarray(jax.random.PRNGKey(7)))
    np.testing.assert_array_equal(np.asarray(as_key(KEY)), np.asarray(KEY))


def test_int_seed_equals_key_everywhere():
    """`key=7` and `key=PRNGKey(7)` draw identical permutations in every
    entry point (the one documented coercion rule)."""
    dm, dm2, g = _dm(15), _dm(16), _grouping()
    k7 = jax.random.PRNGKey(7)
    assert permanova(dm, g, 19, 7) == permanova(dm, g, 19, k7)
    assert anosim(dm, g, 19, 7) == anosim(dm, g, 19, k7)
    assert mantel(dm, dm2, 19, 7) == mantel(dm, dm2, 19, k7)
    a = pcoa(dm, dimensions=3, key=7)
    b = pcoa(dm, dimensions=3, key=k7)
    np.testing.assert_array_equal(np.asarray(a.coordinates),
                                  np.asarray(b.coordinates))


def test_results_record_method_and_key():
    dm, g = _dm(17), _grouping()
    ws = Workspace(dm)
    r = ws.permanova(g, permutations=19, key=7)
    assert r.method == "permanova"
    np.testing.assert_array_equal(np.asarray(r.key),
                                  np.asarray(jax.random.PRNGKey(7)))
    o = ws.pcoa(dimensions=3)
    assert o.method == "fsvd" and o.key is not None
    assert ws.pcoa(dimensions=3, method="eigh").key is None  # deterministic
    # results stay plain frozen dataclasses
    assert dataclasses.is_dataclass(r) and dataclasses.is_dataclass(o)


# --------------------------------------------------------------------------
# pcoa dimensions validation (satellite regression)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("method", ["fsvd", "eigh"])
def test_pcoa_dimensions_validation_consistent(method):
    """Regression: `dimensions <= 0` raises and `dimensions > n` clamps to
    n on BOTH solver paths (fsvd used to silently slice from the bottom of
    the spectrum for negative k)."""
    dm = _dm(18, 20)
    for bad in (0, -3):
        with pytest.raises(ValueError, match="dimensions"):
            pcoa(dm, dimensions=bad, method=method)
        with pytest.raises(ValueError, match="dimensions"):
            Workspace(dm).pcoa(dimensions=bad, method=method)
    r = pcoa(dm, dimensions=55, method=method)       # > n clamps to n
    assert r.coordinates.shape == (20, 20)
    with pytest.raises(ValueError, match="dimensions"):
        permdisp(dm, _grouping(20), permutations=9, dimensions=-1)


def test_pcoa_rejects_mismatched_prebuilt_artifacts():
    """A prebuilt hoist the taken path would silently ignore is an error —
    dropping the O(n²) artifact the caller paid for defeats its point."""
    from repro.core import CenteredGramOperator, materialized_gram
    dm = _dm(19, 16)
    op = CenteredGramOperator.from_distance(dm.data)
    g = materialized_gram(dm.data)
    with pytest.raises(ValueError, match="gram"):
        pcoa(dm, dimensions=3, gram=g)                   # runs matrix-free
    with pytest.raises(ValueError, match="operator"):
        pcoa(dm, dimensions=3, method="eigh", operator=op)
    # matched artifacts are consumed
    a = pcoa(dm, dimensions=3, operator=op)
    b = pcoa(dm, dimensions=3)
    np.testing.assert_array_equal(np.asarray(a.coordinates),
                                  np.asarray(b.coordinates))
    pcoa(dm, dimensions=3, method="eigh", gram=g)


def test_resolve_dimensions_rule():
    assert resolve_dimensions(None, 10) == 9         # scikit-bio: all axes
    assert resolve_dimensions(3, 10) == 3
    assert resolve_dimensions(99, 10) == 10          # clamp
    assert resolve_dimensions(None, 1) == 1          # degenerate floor
    for bad in (0, -1):
        with pytest.raises(ValueError):
            resolve_dimensions(bad, 10)


def test_hoist_counters_across_refresh_generations():
    """refresh() drops every artifact WITH fresh counters: the next
    analysis re-runs each hoist exactly once, and the generation-0
    tallies don't leak into the generation-1 cache."""
    dm, g = _dm(11), _grouping()
    ws = Workspace(dm)
    ws.permanova(g, permutations=19, key=KEY)
    ws.permanova(g, permutations=19, key=KEY)
    gen0 = ws.cache
    assert ws.generation == 0
    assert gen0.counts("gram") == (1, 1)             # one build, one reuse

    ws.refresh()
    assert ws.generation == 1
    assert ws.cache is not gen0                      # a NEW cache object
    assert len(ws.cache) == 0
    assert ws.cache.counts("gram") == (0, 0)         # counters start clean
    assert gen0.counts("gram") == (1, 1)             # old tallies untouched

    r0 = ws.permanova(g, permutations=19, key=KEY)
    r1 = ws.permanova(g, permutations=19, key=KEY)
    assert ws.cache.counts("gram") == (1, 1)         # hoisted exactly once
    assert r0.statistic == r1.statistic

    # re-admitting NEW data through refresh() also restarts the tallies
    ws.refresh(dm=_dm(12))
    assert ws.generation == 2 and ws.cache.counts("gram") == (0, 0)
    ws.permanova(g, permutations=19, key=KEY)
    assert ws.cache.counts("gram") == (0, 1)


def test_eigh_coords_slice_hit_path_exact_counts():
    """A lower-k eigh request is served by SLICING a cached higher-k
    solution: exactly one hit on the higher-k entry, a slice-only build
    of the lower-k entry (the gram/solve pipeline does NOT re-run), and
    the sliced coordinates are bitwise the higher-k solution's prefix."""
    ws = Workspace(_dm(13))
    full = ws.pcoa(dimensions=8, method="eigh")
    k8 = ("coords", 8, "eigh", None)
    assert ws.cache.counts(k8) == (0, 1)
    assert ws.cache.counts("gram") == (0, 1)         # eigh's one solve

    low = ws.pcoa(dimensions=3, method="eigh")
    k3 = ("coords", 3, "eigh", None)
    assert ws.cache.counts(k8) == (1, 1)             # slice source: a HIT
    assert ws.cache.counts(k3) == (0, 1)             # the slice build
    assert ws.cache.counts("gram") == (0, 1)         # and NO re-solve
    np.testing.assert_array_equal(np.asarray(low.coordinates),
                                  np.asarray(full.coordinates)[:, :3])
    np.testing.assert_array_equal(np.asarray(low.eigenvalues),
                                  np.asarray(full.eigenvalues)[:3])

    # the sliced entry is itself cached: ask again, nothing builds
    ws.pcoa(dimensions=3, method="eigh")
    assert ws.cache.counts(k3) == (1, 1)
    assert ws.cache.counts(k8) == (1, 1)             # not consulted again

    # slicing picks the SMALLEST covering solution once several exist
    ws.pcoa(dimensions=12, method="eigh")            # k=12 solve (miss)
    ws.pcoa(dimensions=6, method="eigh")             # 6 ≤ 8 < 12 -> from k8
    assert ws.cache.counts(k8) == (2, 1)
    assert ws.cache.counts(("coords", 12, "eigh", None)) == (0, 1)
