"""Traffic generation, latency arithmetic and the refusal without a chip,
at tiny sizes on the CPU."""

import json
import os
import shutil
import subprocess
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness, reference  # noqa: E402
from benchmarks.chip.data import (abundance_table, count_table,  # noqa: E402
                                  counterpart, make_groups)
from benchmarks.chip.drivers import service  # noqa: E402


def make_cell(name):
    """A cell of ``BENCHMARK.json``, or of ``data/cells.json``: cells built
    and checked here on the CPU whose bounds are not yet measured on the
    chip, so the benchmark does not hold them."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    more = json.loads((Path(__file__).parent / "data" / "cells.json")
                      .read_text())
    for key, entries in more.items():
        bench[key] = bench[key] + entries
    return harness.Cell(name, bench=bench)


@pytest.fixture(scope="module")
def served():
    return make_cell("qiita_mix.open80")


@pytest.mark.parametrize("total", [1, 7, 45, 180])
def test_apportion_sums_to_the_total_in_proportion(total):
    w = {"a": 0.5, "b": 0.3, "c": 0.15, "d": 0.05}
    got = service.apportion(w, total)
    assert sum(got.values()) == total
    for k, c in got.items():
        assert abs(c - total * w[k]) < 1


def test_every_seed_gets_the_same_requests_in_another_order(served):
    cfg, tr = served.config, served.traffic
    decks = []
    for seed in (1, 2 ** 31 + 5):
        d = service.Driver(served, seed, 30.0, print)
        d.plan(np.random.default_rng(seed))
        decks.append(d.requests)
    a, b = ([r[:3] for r in dk] for dk in decks)
    assert Counter(a) == Counter(b)
    assert a != b
    assert len(a) == int(tr["rate_per_s"] * 30.0)
    # partial Mantel only where three studies share a size
    sizes = Counter(s["samples"] for s in cfg["studies"])
    for si, method, _ in a:
        if method == "partial_mantel":
            assert sizes[cfg["studies"][si]["samples"]] >= 3


def test_arrivals_share_their_gaps_and_stay_in_the_window():
    a = service.arrivals(np.random.default_rng(1), 4.0, 30.0)
    b = service.arrivals(np.random.default_rng(2), 4.0, 30.0)
    assert len(a) == len(b) == 120
    assert np.all(np.diff(a) > 0) and a[-1] < 30.0
    assert np.allclose(np.sort(np.diff(np.r_[0, a])),
                       np.sort(np.diff(np.r_[0, b])))
    assert a[-1] == pytest.approx(b[-1])


class _Handle:
    def __init__(self, status, t_done):
        self.status, self.t_done = status, t_done


def test_latency_runs_from_when_a_request_was_due(served):
    d = service.Driver(served, 1, 10.0, lambda m: None)
    d.t0 = 100.0
    d.due = np.array([1.0, 2.0, 3.0, 4.0, 5.0])
    d.handles = [_Handle("done", 101.5), _Handle("done", 102.25),
                 _Handle("rejected", 103.0), _Handle("done", 108.0)]
    d.tiles, d.late = 0, 0.0
    r = d.results()
    lat = [0.5, 0.25, 4.0]
    assert r["attempted"] == 5
    assert r["failed"] == 2          # one rejected, one never submitted
    assert r["metrics"]["latency_p50_s"] == pytest.approx(np.median(lat))
    assert r["metrics"]["latency_p90_s"] == pytest.approx(
        np.percentile(lat, 90))


def test_orders_follow_their_definition():
    import jax
    import jax.numpy as jnp
    o = reference.orders(2 ** 31 - 2, 5, 40)
    bits = np.asarray(jax.random.bits(jax.random.PRNGKey(2 ** 31 - 2),
                                      (5, 40), dtype=jnp.uint32))
    assert np.array_equal(o, np.argsort(bits, axis=-1, kind="stable"))
    assert all(sorted(row) == list(range(40)) for row in o)


def test_reference_statistics_match_scipy_on_a_small_study():
    from scipy.spatial.distance import pdist, squareform
    from scipy.stats import f_oneway
    rng = np.random.default_rng(3)
    g = make_groups(rng, 30)
    x = abundance_table(rng, g, 12)
    sq = reference.braycurtis(x)
    assert np.allclose(sq, squareform(pdist(x.astype(np.float64),
                                            "braycurtis")))
    ref = reference.Reference(sq, g)
    order = rng.permutation(30)
    # PERMANOVA by its textbook sums
    d = squareform(sq)
    i, j = np.triu_indices(30, k=1)
    codes = g[order]
    same = codes[i] == codes[j]
    sizes = np.bincount(codes)
    ss_w = np.sum(np.bincount(codes[i][same], weights=d[same] ** 2) / sizes)
    ss_t = np.sum(d ** 2) / 30
    f = ((ss_t - ss_w) / 2) / (ss_w / 27)
    assert ref.permanova(order) == pytest.approx(f, rel=1e-12)
    # PERMDISP as a one-way ANOVA of distances to centroids
    x10 = ref.coordinates(5)
    v = np.empty(30)
    for k in range(3):
        m = codes == k
        v[m] = np.linalg.norm(x10[m] - x10[m].mean(axis=0), axis=1)
    want = f_oneway(*(v[codes == k] for k in range(3))).statistic
    assert ref.permdisp(order, 5) == pytest.approx(want, rel=1e-10)


def test_harness_refuses_without_a_chip():
    cell = harness.Cell("cohort_bc.mantel")
    with pytest.raises(harness.NoChip):
        harness.devices_for(cell, require_tpu=True)


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "cohort_bc.mantel", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_run_exits_nonzero_and_prints_no_result_off_the_chip():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmarks" / "chip",
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_file_names_every_piece():
    bench = make_cell("cohort_bc.mantel").bench
    here = ROOT / "benchmarks" / "chip"
    for c in bench["configs"]:
        assert (ROOT / c["file"]).exists()
    for w in bench["workloads"]:
        assert (here / "traffic" / f"{w['traffic']}.json").exists()
        assert (here / "limits" / f"{w['name']}.json").exists()
    for m in bench["per_layer"]:
        assert (here / "metrics" / f"{m['name']}.py").exists()


def test_count_tables_are_sparse_integer_and_from_the_seed():
    def make(seed):
        rng = np.random.default_rng(seed)
        g = make_groups(rng, 200, (0.5, 0.22, 0.17, 0.06, 0.05))
        x = count_table(rng, g, 400, 0.05, 1.5)
        return g, x, counterpart(rng, x, g, 450, 0.05, 1.5, 0.5)

    g, x, y = make(2 ** 31 + 3)
    assert x.shape == (200, 400) and y.shape == (200, 450)
    for t in (x, y):
        assert t.dtype == np.float32 and np.all(t == np.round(t))
        assert np.all(t >= 0) and np.all(t.any(axis=1))
    assert 0.02 < np.mean(x > 0) < 0.1
    # the counterpart holds every feature of the table, on its own column
    assert np.all((y > 0).sum(axis=1) >= (x > 0).sum(axis=1))
    _, x2, y2 = make(2 ** 31 + 3)
    assert np.array_equal(x, x2) and np.array_equal(y, y2)
    with pytest.raises(ValueError):
        counterpart(np.random.default_rng(0), x, g, 300, 0.05, 1.5, 0.5)


def test_reference_braycurtis_matches_scipy_on_counts():
    from scipy.spatial.distance import pdist, squareform
    rng = np.random.default_rng(7)
    g = make_groups(rng, 40)
    x = count_table(rng, g, 90, 0.1, 1.5).astype(np.float64)
    want = squareform(pdist(x, "braycurtis"))
    assert np.max(np.abs(reference.braycurtis(x) - want)) < 1e-13
    # two empty rows: 0/0 is taken as 0
    x[:2] = 0.0
    got = reference.braycurtis(x)
    assert got[0, 1] == 0.0 and got[1, 0] == 0.0
    assert np.allclose(got[2:, 2:], squareform(pdist(x[2:], "braycurtis")),
                       rtol=0, atol=1e-13)
