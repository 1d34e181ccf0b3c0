"""The cells ``cohort_uf.mantel`` (the ``unifrac_studies`` driver,
``traffic/mantel_unifrac.json``) and ``cohort_bc.ordinate`` (the
``ordinate_studies`` driver, ``traffic/ordinate.json``) at a tiny size
on the CPU: a sound run is correct, the bfloat16 control is not, and
neither is a run whose tree has one wrong branch length or whose fsvd
cuts a power iteration. Also the seeded trees and the float64 UniFrac
and fsvd references the checks compare with."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from benchmarks.chip import harness, trees  # noqa: E402
from benchmarks.chip.drivers import studies, unifrac_studies  # noqa: E402
from benchmarks.chip.unifrac_reference import (  # noqa: E402
    unweighted_unifrac)

SEED = 2 ** 31 + 23
CELLS = ["cohort_uf.mantel", "cohort_bc.ordinate"]


def tiny(name):
    """The cell of ``BENCHMARK.json`` at a size the CPU runs in seconds."""
    cell = harness.Cell(name)
    cell.config.update(samples=64, features=[32, 40], density=0.3,
                       table_sets=2, permutations=99)
    return cell


def run(name, control=None):
    return harness.run(tiny(name), SEED, 2.0, False, require_tpu=False,
                       log=lambda m: None, control=control,
                       compile_cache=False)


@pytest.mark.parametrize("name", CELLS)
def test_a_sound_run_is_correct(name):
    r = run(name)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    want = {"cohort_uf.mantel": {"dist_err", "stat_err.mantel",
                                 "null_err.mantel", "p_rule_gap.mantel"},
            "cohort_bc.ordinate": {"dist_err", "eig_err",
                                   "stat_err.permanova",
                                   "null_err.permanova",
                                   "p_rule_gap.permanova"}}[name]
    assert set(r["checks"]) == want


@pytest.mark.parametrize("name", CELLS)
def test_the_control_is_not_correct(name):
    r = run(name, control="bfloat16")
    assert not r["correct"], r["checks"]


def test_one_wrong_branch_length_is_caught(monkeypatch):
    """The program is given a tree whose branch above the most common
    V1-V3 OTU is 50% longer than the reference's."""
    from repro.dist import PhyloTree
    setup = unifrac_studies.Driver.setup

    def setup_wrong(self, *args, **kwargs):
        setup(self, *args, **kwargs)
        for (_, tables), arrays, phylo in zip(self.sets, self.trees,
                                              self.phylo):
            parent, length, tips = arrays[0]
            length = length.copy()
            length[tips[np.argmax((tables[0] > 0).sum(axis=0))]] *= 1.5
            phylo[0] = PhyloTree(parent, length, tips)

    monkeypatch.setattr(unifrac_studies.Driver, "setup", setup_wrong)
    r = run("cohort_uf.mantel")
    assert not r["correct"], r["checks"]
    assert r["checks"]["dist_err"]["value"] > \
        r["checks"]["dist_err"]["limit"]


def test_a_solve_short_of_a_power_iteration_is_caught(monkeypatch):
    """The program's fsvd runs one power iteration where the
    configuration's solve runs two."""
    import functools
    import importlib
    pcoa = importlib.import_module("repro.core.pcoa")
    solve = pcoa._randomized_eigh_matfree

    @functools.wraps(solve)
    def one_iteration(op, key, k, **kwargs):
        return solve(op, key, k, power_iters=1)

    monkeypatch.setattr(pcoa, "_randomized_eigh_matfree", one_iteration)
    r = run("cohort_bc.ordinate")
    assert not r["correct"], r["checks"]
    assert r["checks"]["eig_err"]["value"] > r["checks"]["eig_err"]["limit"]


@pytest.mark.parametrize("n, key", [(40, 7), (120, 2 ** 31 - 2)])
def test_fsvd_reference_is_the_programs_solve_in_float64(n, key):
    """On the same key the program's fsvd and ``fsvd_reference`` agree on
    every axis to float32 rounding; the reference in bfloat16 does not."""
    import jax.numpy as jnp
    from benchmarks.chip import fsvd_reference, reference as R
    from repro.core.pcoa import pcoa
    from repro.core.distance_matrix import DistanceMatrix
    rng = np.random.default_rng(n)
    x = (rng.random((n, 30)) < 0.3) * rng.integers(1, 9, (n, 30))
    x[:, 0] += 1
    square = R.braycurtis(x)
    want = fsvd_reference.eigenvalues(square, key, 10)
    got = np.asarray(pcoa(DistanceMatrix(jnp.asarray(square, jnp.float32)),
                          10, "fsvd", key=key).eigenvalues, np.float64)
    assert np.max(np.abs(got - want)) / want[0] < 1e-5
    low = fsvd_reference.eigenvalues(square, key, 10, R.Precision("bfloat16"))
    assert np.max(np.abs(low - want)) / want[0] > 1e-4


def test_tables_are_the_studies_drivers_and_trees_place_shared_otus():
    """A seed gives the ``studies`` driver's tables and keys; each V1-V3
    OTU's tip is, in the V3-V5 tree, the tip of the column holding that
    OTU's counts."""
    cell = tiny("cohort_uf.mantel")
    uf = unifrac_studies.Driver(cell, SEED, 1.0, lambda m: None)
    uf.setup(warm=False)
    bc = studies.Driver(cell, SEED, 1.0, lambda m: None)
    bc.setup(warm=False)
    bc.release()                 # the last to hook engine.finish first
    uf.release()
    assert uf.keys == bc.keys
    for (g, tables), (h, others), arrays in zip(uf.sets, bc.sets, uf.trees):
        assert np.array_equal(g, h)
        assert all(np.array_equal(a, b) for a, b in zip(tables, others))
        x, y = tables
        (_, _, tips0), (parent1, _, tips1) = arrays
        column_of = {t: j for j, t in enumerate(tips1)}
        for f in range(x.shape[1]):
            j = column_of[tips0[f]]
            assert np.array_equal(x[:, f] > 0, y[:, j] > 0)
        assert parent1.size == 2 * y.shape[1] - 1


@pytest.mark.parametrize("tips, new", [(1, 0), (2, 3), (50, 7)])
def test_yule_and_graft_keep_paths(tips, new):
    """Yule trees are rooted and binary; grafting keeps every shared
    tip's distance from the root."""
    from repro.dist import PhyloTree
    rng = np.random.default_rng(tips)
    tree = trees.yule(rng, tips, 0.03)
    parent, length, leaves = tree
    t = PhyloTree(*tree)
    assert t.num_tips == tips and t.num_branches == 2 * tips - 2
    columns = rng.permutation(tips + new)
    grafted = trees.graft(rng, tree, columns, 0.03)
    u = PhyloTree(*grafted)
    assert u.num_branches == 2 * (tips + new) - 2

    def depth(p, l, v):
        total = 0.0
        while p[v] != -1:
            total, v = total + l[v], p[v]
        return total

    for f in range(tips):
        assert depth(grafted[0], grafted[1], grafted[2][columns[f]]) == \
            pytest.approx(depth(parent, length, leaves[f]))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_reference_matches_the_programs_oracle(seed):
    """The benchmark's UniFrac (sparse walk, blocked product) and the
    program's eager oracle (``repro.dist.unifrac_ref``) agree."""
    from repro.dist.unifrac_ref import unweighted_unifrac_ref
    rng = np.random.default_rng(seed)
    tree = trees.yule(rng, 60, 0.03)
    x = (rng.random((25, 60)) < 0.1) * rng.integers(1, 9, (25, 60))
    x[3] = 0
    want = unweighted_unifrac_ref(x, *tree)
    assert np.max(np.abs(unweighted_unifrac(x, tree) - want)) < 1e-12
