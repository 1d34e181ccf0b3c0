"""Comparisons of a run's answers with the reference's.

An answer is what a user of the system reads back: distances, a test's
statistic and p-value (with its null draws, where the run hands them
over), an ordination's eigenvalues. Each comparison adds one number to
the run's ``Checks``; the limits live in ``limits/<cell>.json``.

* ``dist_err``: the largest absolute gap of a distance.
* ``stat_err.<method>``: the gap of the observed statistic, over the
  test's scale (the largest |value| among the observed statistic and the
  reference's draws).
* ``null_err.<method>``: the largest gap of a null draw on the same
  order, over the same scale.
* ``p_rule_gap.<method>``: how many draws the p-value's count differs
  from the count of the run's own draws at least as extreme as its own
  observed statistic; exact, so its limit is 0.
* ``p_gap.<method>``: how many draws the p-value's count lies outside
  the counts the reference allows, its draws and observed value moved by
  up to the ``stat_err`` limit; exact, so its limit is 0.
* ``eig_err``: the largest gap of the leading eigenvalues, over the
  largest eigenvalue: every requested axis of an exact solve; of a
  randomized one only the axes that stand clear of the noise floor (one
  fewer than the groups).
"""

from __future__ import annotations

import numpy as np

from benchmarks.chip import reference as R


def test_scale(observed, draws) -> float:
    draws = np.asarray(draws, dtype=np.float64)
    return max(abs(float(observed)),
               float(np.max(np.abs(draws))) if draws.size else 0.0)


def distances(checks, got, want_square) -> None:
    want = R.condensed(want_square)
    got = np.asarray(got, dtype=np.float64)
    checks.add("dist_err", float(np.max(np.abs(got - want))))


def permutation_test(checks, answer, ref_observed, ref_draws, rows=None):
    """``answer``: method, alternative, permutations, statistic, p_value,
    and optionally ``draws`` (all K of the run's draws). ``ref_draws``
    are the reference's draws on ``rows`` (all K when ``rows`` is None).
    """
    method = answer["method"]
    scale = test_scale(ref_observed, ref_draws)
    checks.add(f"stat_err.{method}",
               abs(answer["statistic"] - ref_observed) / scale)
    k = answer["permutations"]
    count = R.p_count(answer["p_value"], k)
    draws = answer.get("draws")
    if draws is not None:
        draws = np.asarray(draws, dtype=np.float64)
        picked = draws if rows is None else draws[rows]
        checks.add(f"null_err.{method}",
                   float(np.max(np.abs(picked - ref_draws))) / scale)
        own = R.exceeding(answer["statistic"], draws, answer["alternative"])
        checks.add(f"p_rule_gap.{method}", abs(count - own))
    if rows is None:
        limit = checks.limits.get(f"stat_err.{method}") or 0.0
        lo, hi = R.count_band(ref_observed, ref_draws,
                              answer["alternative"], limit * scale)
        checks.add(f"p_gap.{method}", max(0, lo - count, count - hi))


def eigenvalues(checks, got, want, top) -> None:
    got = np.asarray(got, dtype=np.float64)[:top]
    want = np.asarray(want, dtype=np.float64)
    checks.add("eig_err", float(np.max(np.abs(got - want[:top])) / want[0]))


def reference_answer(method, ref, operands, key, permutations,
                     alternative, prec):
    """The answer the reference gives in ``prec``: what the control puts
    in the program's place."""
    observed, draws = R.test(method, ref, operands, key, permutations)
    count = R.exceeding(observed, draws, alternative)
    return {"method": method, "alternative": alternative,
            "permutations": permutations, "statistic": observed,
            "p_value": float(np.float32(count + 1)
                             / np.float32(permutations + 1)),
            "draws": draws}
