"""The output check at tiny sizes on the CPU: a sound run is correct;
the control (the reference in bfloat16 in the program's place) and each
fault a cell can have, planted under the timed path, are not.

These runs skip the harness's look for a chip and drive the rest of a
run: set-up, window, release, comparison. The limits are the cells' own
(``limits/<cell>.json``)."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from benchmarks.chip import harness  # noqa: E402

SEED = 2 ** 31 + 11
CELLS = ["cohort_bc.mantel", "qiita_mix.open80"]


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


def tiny(name):
    cell = make_cell(name)
    if name == "cohort_bc.mantel":
        cell.config.update(samples=64, features=[32, 40], density=0.3,
                           table_sets=2, permutations=99)
    else:
        cell.config.update(features=32, studies=[
            {"id": f"t{i}", "samples": n}
            for i, n in enumerate([16, 32, 16, 64, 16, 32, 32, 64])])
        cell.traffic.update(rate_per_s=6.0, drain_s=30)
    return cell


def run(name, control=None):
    return harness.run(tiny(name), SEED, 2.0, False, require_tpu=False,
                       log=lambda m: None, control=control,
                       compile_cache=False)


@pytest.mark.parametrize("name", CELLS)
def test_a_sound_run_is_correct(name):
    r = run(name)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert r["failed"] == 0 and r["attempted"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_the_control_is_not_correct(name):
    r = run(name, control="bfloat16")
    assert not r["correct"], r["checks"]


def _answer_altered(monkeypatch):
    """The p-value altered where the program produces it."""
    from repro.stats import engine
    orig = engine.p_value
    monkeypatch.setattr(engine, "p_value", lambda c, k: orig(c + 5, k))


def _half_batch(monkeypatch):
    """Half of each batch of draws left out, the rest counted twice."""
    from repro.stats import engine
    null, tile = engine._null_distribution, engine.tile_statistics

    def half(v):
        h = v.shape[0] // 2
        return v.at[h:].set(v[:v.shape[0] - h])

    def null_half(*args, **kwargs):
        observed, permuted = null(*args, **kwargs)
        return observed, half(permuted)

    monkeypatch.setattr(engine, "_null_distribution", null_half)
    monkeypatch.setattr(engine, "tile_statistics",
                        lambda *a, **k: half(tile(*a, **k)))


def _state_unchanged(monkeypatch):
    """Every draw returns the first draw's state: the null never moves."""
    from repro.stats import engine
    null, tile = engine._null_distribution, engine.tile_statistics

    def null_stuck(*args, **kwargs):
        observed, permuted = null(*args, **kwargs)
        return observed, permuted.at[:].set(permuted[0])

    monkeypatch.setattr(engine, "_null_distribution", null_stuck)
    monkeypatch.setattr(engine, "tile_statistics",
                        lambda *a, **k: (lambda v: v.at[:].set(v[0]))(
                            tile(*a, **k)))


FAULTS = {"answer_altered": _answer_altered, "half_batch": _half_batch,
          "state_unchanged": _state_unchanged}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_a_planted_fault_is_caught(name, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    r = run(name)
    assert not r["correct"], r["checks"]


def test_a_nan_reading_is_not_hidden_by_a_later_sound_one():
    c = harness.Checks({"a": 1.0})
    c.add("a", float("nan"))
    c.add("a", 0.5)
    assert not c.ok
    assert np.isnan(c.values["a"])


def test_missing_null_draws_fail_the_check(monkeypatch):
    """A program whose p-value no longer comes from ``engine.finish``:
    no draws reach the benchmark, and the run says so, not crashes."""
    from benchmarks.chip.drivers import studies
    setup = studies.Driver.setup

    def setup_unhooked(self, *args, **kwargs):
        setup(self, *args, **kwargs)
        self._engine.finish = self._finish

    monkeypatch.setattr(studies.Driver, "setup", setup_unhooked)
    r = run("cohort_bc.mantel")
    assert not r["correct"], r["checks"]
    assert np.isnan(r["checks"]["null_err.mantel"]["value"])


def test_checks_fail_without_a_limit_and_on_nan():
    c = harness.Checks({"a": 1.0})
    c.add("a", 0.5)
    assert c.ok
    c.add("a", float("nan"))
    assert not c.ok
    c = harness.Checks({})
    c.add("b", 0.0)
    assert not c.ok
    assert np.isnan(harness.Checks({"a": 1}).values.get("a", np.nan))
