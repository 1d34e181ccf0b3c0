"""Closed-loop library studies: one user, one study after another.

A study goes from feature tables on the host to its answers on the
host: ``Workspace.from_features`` for each table, then the traffic's
analyses in order. The window runs whole studies back to back and
``study_s`` is the span of the studies that finished inside it over
their count (the first to finish, where none did).

Traffic keys: ``tables`` per study (the first is analysed; the others
are the Mantel family's fixed operands, the same samples' tables over
other regions), ``analyses`` (each a ``method`` and, for a test, its
``alternative``), ``checked_studies`` and ``checked_draws``.
Configuration keys: ``samples``, ``features`` (one width per table of a
study), ``metric``, ``density``, ``mean_log_count``, ``region_sigma``,
``group_shares``, ``table_sets`` (studies cycle through them),
``permutations``, ``pcoa`` (``pcoa_method``, ``dimensions``).

A test's null draws reach the host only where the program turns them
into its p-value, ``repro.stats.engine.finish``; the driver reads them
there. Where none arrive, the check of the draws fails, saying so.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.chip import checking
from benchmarks.chip import reference as R
from benchmarks.chip.data import (count_table, counterpart, make_groups,
                                  program_key)
from benchmarks.chip.harness import annotate

MAX_STUDIES = 256


class Driver:
    def __init__(self, cell, seed, seconds, log):
        self.cell, self.seed, self.log = cell, seed, log
        self.cfg, self.tr = cell.config, cell.traffic
        self.done = []          # (set index, key, answers) per study

    # -- set-up --------------------------------------------------------------
    def setup(self, warm=True):
        from repro.stats import engine
        cfg, tr = self.cfg, self.tr
        t = time.perf_counter()
        rng = np.random.default_rng(self.seed)
        widths = cfg["features"][:tr["tables"]]
        self.sets = []
        for _ in range(cfg["table_sets"]):
            g = make_groups(rng, cfg["samples"], cfg["group_shares"])
            x = count_table(rng, g, widths[0], cfg["density"],
                            cfg["mean_log_count"])
            tables = [x] + [counterpart(rng, x, g, w, cfg["density"],
                                        cfg["mean_log_count"],
                                        cfg["region_sigma"])
                            for w in widths[1:]]
            self.sets.append((g, tables))
        self.keys = [program_key(rng) for _ in range(MAX_STUDIES)]
        self.log(f"tables made in {time.perf_counter() - t:.2f} s")
        self._engine = engine
        self._finish = getattr(engine, "finish", None)
        self._draws = []
        if self._finish is not None:
            def finish(orig_stat, permuted_stats, *args, **kwargs):
                self._draws.append(permuted_stats)
                return self._finish(orig_stat, permuted_stats, *args,
                                    **kwargs)

            engine.finish = finish
        # every program the window runs, once: K is static in the engine
        warm_key = program_key(rng)
        if warm:
            self._study(len(self.sets) - 1, warm_key)

    def _analysis(self, ws, groups, a, key):
        cfg = self.cfg
        method, k = a["method"], cfg["permutations"]
        if method == "pcoa":
            r = ws[0].pcoa(dimensions=cfg["pcoa"]["dimensions"],
                           method=cfg["pcoa"]["pcoa_method"], key=key)
            return {"method": method,
                    "eigenvalues": np.asarray(r.eigenvalues)}
        if method == "mantel":
            r = ws[0].mantel(ws[1], k, key=key, alternative=a["alternative"])
        elif method == "partial_mantel":
            r = ws[0].partial_mantel(ws[1], ws[2], k, key=key,
                                     alternative=a["alternative"])
        elif method == "permdisp":
            r = ws[0].permdisp(groups, k, key=key,
                               dimensions=cfg["pcoa"]["dimensions"],
                               method=cfg["pcoa"]["pcoa_method"])
        else:
            r = getattr(ws[0], method)(groups, k, key=key)
        draws = self._draws[0] if len(self._draws) == 1 else None
        return {"method": method, "alternative": a["alternative"],
                "permutations": k, "statistic": r.statistic,
                "p_value": r.p_value, "draws": draws}

    def _study(self, index, key):
        from repro.api.workspace import Workspace
        groups, tables = self.sets[index]
        with annotate("bench.workspace"):
            ws = [Workspace.from_features(t, metric=self.cfg["metric"])
                  for t in tables]
        answers = []
        for a in self.tr["analyses"]:
            self._draws.clear()
            with annotate(f"bench.{a['method']}"):
                answers.append(self._analysis(ws, groups, a, key))
        return ws, answers

    # -- the window ----------------------------------------------------------
    def window(self, seconds):
        t0 = time.perf_counter()
        ends, last = [], 0.0
        i = 0
        while i < MAX_STUDIES:
            start = time.perf_counter() - t0
            if ends and start + last > seconds:
                break                    # it could not finish in the window
            index = i % len(self.sets)
            with annotate("bench.study"):
                ws, answers = self._study(index, self.keys[i])
            end = time.perf_counter() - t0
            last = end - start
            ends.append(end)
            self.done.append({"set": index, "key": self.keys[i],
                              "answers": answers,
                              "condensed": [w.condensed() for w in ws]})
            i += 1
            if end > seconds:
                break
        inside = [e for e in ends if e <= seconds] or ends[:1]
        self.done = self.done[:len(inside)]
        n = self.cfg["samples"]
        tests = [{"method": a["method"], "n": n,
                  "permutations": self.cfg["permutations"]}
                 for _ in ends for a in self.tr["analyses"]
                 if a["method"] != "pcoa"]
        self.log(f"{len(ends)} studies, ends (s from window start): "
                 f"{ends[:8]!r}")
        self._result = {"metrics": {"study_s": inside[-1] / len(inside)},
                "attempted": len(inside), "failed": 0,
                "facts": {"studies": len(ends), "tests": tests}}

    def drain(self):
        pass

    def results(self):
        return self._result

    def release(self):
        """Answers to the host; the program's state goes."""
        if self._finish is not None:
            self._engine.finish = self._finish
        for s in self.done:
            s["condensed"] = [np.asarray(c) for c in s["condensed"]]
            for a in s["answers"]:
                if a.get("draws") is not None:
                    a["draws"] = np.asarray(a["draws"])

    # -- the output check ----------------------------------------------------
    def check(self, checks, control=None):
        """The studies finished in the window, or ``checked_studies`` of
        them drawn from the seed; of each test, ``checked_draws`` draws
        drawn from the seed, and every one where ``control`` is set."""
        t = time.perf_counter()
        rng = np.random.default_rng([self.seed, 1])
        picked = sorted(rng.choice(len(self.done),
                                   min(self.tr["checked_studies"],
                                       len(self.done)), replace=False))
        k = self.cfg["permutations"]
        for i in picked:
            s = self.done[i]
            groups, tables = self.sets[s["set"]]
            squares = [R.braycurtis(t) for t in tables]
            refs = [R.Reference(sq, groups) for sq in squares]
            if control is not None:
                prec = R.Precision(control)
                low = [R.braycurtis(t, prec) for t in tables]
                lrefs = [R.Reference(sq, groups, prec) for sq in low]
                s = {"condensed": [R.condensed(sq) for sq in low],
                     "answers": [self._reference_answer(a, lrefs, s["key"],
                                                        prec)
                                 for a in s["answers"]]}
            for got, want in zip(s["condensed"], squares):
                checking.distances(checks, got, want)
            rows = np.sort(rng.choice(k, min(self.tr["checked_draws"], k),
                                      replace=False))
            for a in s["answers"]:
                if a["method"] == "pcoa":
                    top = len(self.cfg["group_shares"]) - 1
                    checking.eigenvalues(checks, a["eigenvalues"],
                                         refs[0].eigenvalues(top), top)
                    continue
                if a.get("draws") is None:
                    self.log(f"{a['method']}: no null draws reached the "
                             f"host through repro.stats.engine.finish, so "
                             f"none can be checked")
                    checks.add(f"null_err.{a['method']}", float("nan"))
                ops = self._operands(a["method"], refs)
                obs, draws = R.test(a["method"], refs[0], ops,
                                    self.done[i]["key"], k, rows=rows)
                checking.permutation_test(checks, a, obs, draws, rows)
        self.log(f"{len(picked)} studies checked in "
                 f"{time.perf_counter() - t:.2f} s")

    def _operands(self, method, refs):
        return {"other": refs[1] if len(refs) > 1 else None,
                "control": refs[2] if len(refs) > 2 else None,
                "dimensions": self.cfg["pcoa"]["dimensions"]}

    def _reference_answer(self, a, refs, key, prec):
        if a["method"] == "pcoa":
            return {"method": "pcoa",
                    "eigenvalues": refs[0].eigenvalues(
                        self.cfg["pcoa"]["dimensions"])}
        return checking.reference_answer(
            a["method"], refs[0], self._operands(a["method"], refs), key,
            a["permutations"], a["alternative"], prec)
