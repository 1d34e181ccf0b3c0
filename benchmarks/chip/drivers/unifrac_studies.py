"""Closed-loop studies on a tree metric: ``studies`` with each table's tree.

The tables come from the same generator, keys and seed stream as the
``studies`` driver's, so a seed gives the tables of the Bray–Curtis
cells. Each table set also gets its trees (``trees.py``, from a stream
of the seed of their own): a Yule tree over the first table's OTUs, and
for each further table that tree with the table's own OTUs grafted on.
A study sends each table with its tree to ``Workspace.from_features``
and runs the traffic's analyses, as ``studies`` does.

The check compares the window's distances and tests with the float64
UniFrac of ``unifrac_reference.py``, computed once per table set, in the
place of ``studies``' Bray–Curtis; the permutation tests are compared
as ``studies`` compares them (no ordination: the traffic runs tests).
Configuration keys beyond ``studies``': ``tree``
(``mean_branch_length``). The result's facts add ``tree_hoists``: the
(samples, tips, branches) of each table of a study, what the tree
hoist's roofline is counted from; and ``executions``: how many times a
study runs each program of the hoist and of production, as the program
counts them over the window (``perstudy.runs``), which the per-layer
readers weigh each program's whole executions by.
"""

from __future__ import annotations

import copy
import time

import numpy as np

from benchmarks.chip import checking, perstudy
from benchmarks.chip import reference as R
from benchmarks.chip import trees
from benchmarks.chip.data import (count_table, counterpart, make_groups,
                                  program_key)
from benchmarks.chip.drivers import studies
from benchmarks.chip.harness import annotate
from benchmarks.chip.unifrac_reference import unweighted_unifrac


class Driver(studies.Driver):
    def setup(self, warm=True):
        # a program without trees fails here, before any table is made
        from repro.dist import PhyloTree
        from repro.stats import engine
        cfg, tr = self.cfg, self.tr
        t = time.perf_counter()
        rng = np.random.default_rng(self.seed)
        grow = np.random.default_rng([self.seed, 2])
        mean = cfg["tree"]["mean_branch_length"]
        widths = cfg["features"][:tr["tables"]]
        self.sets, self.trees, self.phylo = [], [], []
        for _ in range(cfg["table_sets"]):
            g = make_groups(rng, cfg["samples"], cfg["group_shares"])
            x = count_table(rng, g, widths[0], cfg["density"],
                            cfg["mean_log_count"])
            tables, arrays = [x], [trees.yule(grow, widths[0], mean)]
            for w in widths[1:]:
                # counterpart's first draw places x's OTUs among its columns
                columns = copy.deepcopy(rng).permutation(w)
                tables.append(counterpart(rng, x, g, w, cfg["density"],
                                          cfg["mean_log_count"],
                                          cfg["region_sigma"]))
                arrays.append(trees.graft(grow, arrays[0], columns, mean))
            self.sets.append((g, tables))
            self.trees.append(arrays)
            self.phylo.append([PhyloTree(*a) for a in arrays])
        self.keys = [program_key(rng) for _ in range(studies.MAX_STUDIES)]
        self.log(f"tables and trees made in {time.perf_counter() - t:.2f} s")
        self._engine = engine
        self._finish = getattr(engine, "finish", None)
        self._draws = []
        if self._finish is not None:
            def finish(orig_stat, permuted_stats, *args, **kwargs):
                self._draws.append(permuted_stats)
                return self._finish(orig_stat, permuted_stats, *args,
                                    **kwargs)

            engine.finish = finish
        self._squares = {}
        warm_key = program_key(rng)
        if warm:
            self._study(len(self.sets) - 1, warm_key)

    def _study(self, index, key):
        from repro.api.workspace import Workspace
        groups, tables = self.sets[index]
        with annotate("bench.workspace"):
            ws = [Workspace.from_features(t, metric=self.cfg["metric"],
                                          tree=tree)
                  for t, tree in zip(tables, self.phylo[index])]
        answers = []
        for a in self.tr["analyses"]:
            self._draws.clear()
            with annotate(f"bench.{a['method']}"):
                answers.append(self._analysis(ws, groups, a, key))
        return ws, answers

    def window(self, seconds):
        modules = ("jit__tree_hoist", "jit__panel_stats")
        before = perstudy.runs(modules)
        super().window(seconds)
        facts = self._result["facts"]
        n = self.cfg["samples"]
        facts.update(tree_hoists=[[n, int(a[2].size),
                                   int(np.sum(a[0] != -1))]
                                  for a in self.trees[0]],
                     executions=perstudy.per_study(
                         before, perstudy.runs(modules), facts["studies"]))

    # -- the output check ----------------------------------------------------
    def squares(self, index, prec=R.FLOAT64):
        """The reference's matrices of table set ``index``, made once."""
        key = (index, prec.name)
        if key not in self._squares:
            _, tables = self.sets[index]
            self._squares[key] = [unweighted_unifrac(t, a, prec) for t, a
                                  in zip(tables, self.trees[index])]
        return self._squares[key]

    def check(self, checks, control=None):
        """``studies``' check, on the UniFrac reference."""
        t = time.perf_counter()
        rng = np.random.default_rng([self.seed, 1])
        picked = sorted(rng.choice(len(self.done),
                                   min(self.tr["checked_studies"],
                                       len(self.done)), replace=False))
        k = self.cfg["permutations"]
        for i in picked:
            s = self.done[i]
            groups = self.sets[s["set"]][0]
            want = self.squares(s["set"])
            refs = [R.Reference(sq, groups) for sq in want]
            if control is not None:
                prec = R.Precision(control)
                low = self.squares(s["set"], prec)
                lrefs = [R.Reference(sq, groups, prec) for sq in low]
                s = {"condensed": [R.condensed(sq) for sq in low],
                     "answers": [self._reference_answer(a, lrefs, s["key"],
                                                        prec)
                                 for a in s["answers"]]}
            for got, sq in zip(s["condensed"], want):
                checking.distances(checks, got, sq)
            rows = np.sort(rng.choice(k, min(self.tr["checked_draws"], k),
                                      replace=False))
            for a in s["answers"]:
                if a.get("draws") is None:
                    self.log(f"{a['method']}: no null draws reached the "
                             f"host through repro.stats.engine.finish, so "
                             f"none can be checked")
                    checks.add(f"null_err.{a['method']}", float("nan"))
                obs, draws = R.test(a["method"], refs[0],
                                    self._operands(a["method"], refs),
                                    self.done[i]["key"], k, rows=rows)
                checking.permutation_test(checks, a, obs, draws, rows)
        self.log(f"{len(picked)} studies checked in "
                 f"{time.perf_counter() - t:.2f} s")
