"""Closed-loop ordination studies: ``studies`` with the PCoA checked
against the same randomized solve in float64.

``studies`` compares an ordination with the exact eigenvalues, and so
only its leading axes. A randomized solve of a few power iterations
misses those by how far its subspace is from converged, which on the
HMP cohort is more than bfloat16 rounding moves them, so that check
cannot tell a sound solve from one in bfloat16. Here every requested
eigenvalue is compared with ``fsvd_reference.py``: the configuration's
fsvd on the same sketch, in float64 (``eig_err``, over the largest
eigenvalue). The tables, the window and the tests and their check are
``studies``'.

The result's facts add ``executions``: how many times a study runs each
program of the fsvd solve, as the program counts them over the window
(``perstudy.runs``), which ``pcoa_ms`` weighs each program's whole
executions by.
"""

from __future__ import annotations

import numpy as np

from benchmarks.chip import checking, fsvd_reference, perstudy
from benchmarks.chip import reference as R
from benchmarks.chip.drivers import studies

MODULES = ("jit__randomized_eigh_matfree",)


class Driver(studies.Driver):
    def window(self, seconds):
        before = perstudy.runs(MODULES)
        super().window(seconds)
        facts = self._result["facts"]
        facts["executions"] = perstudy.per_study(
            before, perstudy.runs(MODULES), facts["studies"])

    # -- the output check ----------------------------------------------------
    def check(self, checks, control=None):
        """``studies``' check of the distances and tests; the eigenvalues
        of the studies it picks against ``fsvd_reference``."""
        done = self.done
        self.done = [dict(s, answers=[a for a in s["answers"]
                                      if a["method"] != "pcoa"])
                     for s in done]
        try:
            super().check(checks, control)
        finally:
            self.done = done
        # the studies ``studies.check`` picks: the first draw of its stream
        rng = np.random.default_rng([self.seed, 1])
        picked = sorted(rng.choice(len(done),
                                   min(self.tr["checked_studies"],
                                       len(done)), replace=False))
        k = self.cfg["pcoa"]["dimensions"]
        squares = {}
        for i in picked:
            s = done[i]
            for a in s["answers"]:
                if a["method"] != "pcoa":
                    continue
                want = self._eigenvalues(squares, s["set"], s["key"], k)
                got = (a["eigenvalues"] if control is None else
                       self._eigenvalues(squares, s["set"], s["key"], k,
                                         R.Precision(control)))
                checking.eigenvalues(checks, got, want, k)

    def _eigenvalues(self, squares, index, key, k, prec=R.FLOAT64):
        if (index, prec.name) not in squares:
            squares[index, prec.name] = R.braycurtis(self.sets[index][1][0],
                                                     prec)
        return fsvd_reference.eigenvalues(squares[index, prec.name], key, k,
                                          prec)
