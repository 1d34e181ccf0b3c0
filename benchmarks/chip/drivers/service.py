"""Open-loop traffic through ``AnalysisService``: many users, many
studies.

Set-up uploads the configuration's studies. The window submits
requests on a fixed schedule whatever the service does, and drives the
service's loop between arrivals; after it closes, the loop runs on
until every request due in the window has ended, for at most
``drain_s``. A request's latency runs from when it was due to when its
result was on the host.

Every seed gets the same requests and the same gaps between arrivals,
in another order: the deck of requests is apportioned from the shares
(largest remainders, so it is fixed by the counts), and the gaps are
the quantiles of an exponential at ``rate_per_s``, shuffled. So the
work of a run does not depend on its seed; its data, order and keys do.

Traffic keys: ``rate_per_s``, ``zipf_s`` (popularity over the studies in
the configuration's order), ``methods`` and ``permutations`` (shares),
``unrelated_column_share`` (the share of grouping tests of a label
column unrelated to the table, whose p-values lie anywhere in (0, 1]),
``checked_requests``, ``drain_s``. Configuration keys: ``studies``
(``id``, ``samples``), ``features``, ``metric``, ``zero_share``,
``group_shares``, ``max_sessions``, ``pcoa`` and ``permdisp``
(``dimensions``, ``pcoa_method``).
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks.chip import checking
from benchmarks.chip import reference as R
from benchmarks.chip.data import abundance_table, make_groups, program_key
from benchmarks.chip.harness import annotate

GROUPING = ("permanova", "anosim", "permdisp")
MANTEL = ("mantel", "partial_mantel")
ALTERNATIVE = {"permanova": "greater", "anosim": "greater",
               "permdisp": "greater", "mantel": "two-sided",
               "partial_mantel": "two-sided"}


def apportion(weights: dict, total: int) -> dict:
    """Whole counts summing to ``total`` in the ratio of ``weights``
    (largest remainders; ties to the earlier key)."""
    keys = list(weights)
    w = np.array([weights[k] for k in keys], dtype=np.float64)
    exact = total * w / w.sum()
    counts = np.floor(exact).astype(int)
    order = sorted(range(len(keys)), key=lambda i: (-(exact[i] - counts[i]),
                                                    i))
    for i in order[:total - counts.sum()]:
        counts[i] += 1
    return {k: int(c) for k, c in zip(keys, counts) if c}


def deck(cfg, tr, total: int):
    """The window's requests as (study index, method, K), in a fixed
    order; a study whose size has fewer than three studies takes a
    Mantel test where the shares ask for a partial one."""
    studies = cfg["studies"]
    pop = np.arange(1, len(studies) + 1) ** -float(tr["zipf_s"])
    weights = {}
    for si, s in enumerate(studies):
        peers = sum(1 for t in studies if t["samples"] == s["samples"])
        for method, wm in tr["methods"].items():
            if method == "partial_mantel" and peers < 3:
                method = "mantel"
            ks = ({None: 1.0} if method == "pcoa" else
                  {int(k): v for k, v in tr["permutations"].items()})
            for k, wk in ks.items():
                cell = (si, method, k)
                weights[cell] = weights.get(cell, 0.0) + pop[si] * wm * wk
    counts = apportion(weights, total)
    return [cell for cell, c in counts.items() for _ in range(c)]


def arrivals(rng, rate: float, seconds: float):
    """Due times inside the window: the gaps are the exponential's
    quantiles at (i + ½)/N, shuffled."""
    total = max(int(math.floor(rate * seconds)), 1)
    q = (np.arange(total) + 0.5) / total
    gaps = -np.log1p(-q) / rate
    due = np.cumsum(rng.permutation(gaps))
    return due * (seconds * total / (total + 1) / due[-1])


class Driver:
    def __init__(self, cell, seed, seconds, log):
        self.cell, self.seed, self.log = cell, seed, log
        self.seconds = seconds
        self.cfg, self.tr = cell.config, cell.traffic

    # -- set-up --------------------------------------------------------------
    def _data(self, rng):
        """Per study: its table and two label columns, the groups its
        table was drawn from and a column that has nothing to do with
        it (as a batch number)."""
        cfg = self.cfg
        out = {}
        for s in cfg["studies"]:
            g = make_groups(rng, s["samples"], cfg["group_shares"])
            table = abundance_table(rng, g, cfg["features"],
                                    zero_share=cfg["zero_share"])
            batch = make_groups(rng, s["samples"], cfg["group_shares"])
            out[s["id"]] = ((g, batch), table)
        return out

    def _service(self, data, **options):
        from repro.serve import AnalysisService, ServeConfig
        svc = AnalysisService(ServeConfig(
            max_sessions=self.cfg["max_sessions"], **options))
        for sid, (_, table) in data.items():
            svc.upload(sid, features=table, metric=self.cfg["metric"])
        return svc

    def _peers(self, si):
        studies = self.cfg["studies"]
        n = studies[si]["samples"]
        same = [i for i, s in enumerate(studies) if s["samples"] == n]
        at = same.index(si)
        return [studies[same[(at + j) % len(same)]]["id"]
                for j in (1, 2)]

    def _submit(self, svc, data, req):
        si, method, k, key, column = req
        sid = self.cfg["studies"][si]["id"]
        kw = {"key": key}
        if method == "pcoa":
            kw.update(dimensions=self.cfg["pcoa"]["dimensions"],
                      pcoa_method=self.cfg["pcoa"]["pcoa_method"])
        else:
            kw["permutations"] = k
        if method in GROUPING:
            kw["grouping"] = data[sid][0][column]
        if method == "permdisp":
            kw.update(dimensions=self.cfg["permdisp"]["dimensions"],
                      pcoa_method=self.cfg["permdisp"]["pcoa_method"])
        if method in MANTEL:
            other, control = self._peers(si)
            kw["other"] = other
            if method == "partial_mantel":
                kw["control"] = control
        return svc.submit(sid, method, **kw)

    def setup(self, warm=True):
        rng = np.random.default_rng(self.seed)
        self.data = self._data(rng)
        self.plan(rng)
        if warm:
            self.warm()
        self.svc = self._service(self.data)

    def plan(self, rng):
        """The window's due times and requests (study, method, K, key)."""
        self.due = arrivals(rng, self.tr["rate_per_s"], self.seconds)
        cells = deck(self.cfg, self.tr, len(self.due))
        share = float(self.tr["unrelated_column_share"])
        self.requests = [cells[i] + (program_key(rng),
                                     int(rng.random() < share))
                         for i in rng.permutation(len(cells))]

    def warm(self):
        """Every (size, method, K) the traffic can send, on a service of
        its own, each K of a grouping test in a lane of its own, so that
        a lone request's last, padded tile runs for every K. Then, per
        size, a chain in one lane of B requests of each K (B the tile's
        rows): a K prime to B starts one request at every offset of a
        tile, so the service assembles every split of a tile between two
        requests before the window does."""
        data = self._data(np.random.default_rng([self.seed, 2]))
        warm = self._service(data, timeout_s=None, max_queue=4096)
        seen, handles = set(), []
        ks = [int(k) for k in self.tr["permutations"]]
        chain = [k for k in ks for _ in range(warm.config.batch_size)]
        for si, s in enumerate(self.cfg["studies"]):
            n = s["samples"]
            for method in self.tr["methods"]:
                if method == "partial_mantel" and len(set(
                        self._peers(si) + [s["id"]])) < 3:
                    continue
                if (n, method) in seen:
                    continue
                seen.add((n, method))
                for j, k in enumerate([None] if method == "pcoa" else ks):
                    handles.append(self._submit(warm, data,
                                                (si, method, k, 1, j % 2)))
            if n not in seen:
                seen.add(n)
                handles += [self._submit(warm, data,
                                         (si, "permanova", k, 1, 0))
                            for k in chain]
        warm.run()
        bad = [h.status for h in handles if h.status != "done"]
        if bad:
            raise RuntimeError(f"warm-up requests ended {bad}")

    # -- the window ----------------------------------------------------------
    def window(self, seconds):
        svc = self.svc
        self.tiles0 = svc.scheduler.tiles_run
        self.t0 = t0 = time.perf_counter()
        self.handles, late = [], 0.0
        i, total = 0, len(self.due)
        while True:
            now = time.perf_counter() - t0
            while i < total and self.due[i] <= now:
                with annotate("bench.submit"):
                    h = self._submit(svc, self.data, self.requests[i])
                late = max(late, time.perf_counter() - t0 - self.due[i])
                self.handles.append(h)
                i += 1
            if now >= seconds:
                break
            with annotate("bench.step"):
                busy = svc.step()
            if not busy and i < total:
                wait = min(self.due[i], seconds) - (time.perf_counter() - t0)
                if wait > 0:
                    with annotate("bench.wait_for_arrival"):
                        time.sleep(wait)
        self.tiles = svc.scheduler.tiles_run - self.tiles0
        self.late = late

    def drain(self):
        svc = self.svc
        end = self.t0 + self.seconds + self.tr["drain_s"]
        while (any(not h.done for h in self.handles)
               and time.perf_counter() < end):
            if not svc.step():
                break

    def results(self):
        lat, failed = [], 0
        for h, due in zip(self.handles, self.due):
            if h.status == "done":
                lat.append(h.t_done - (self.t0 + due))
            else:
                failed += 1
        failed += len(self.due) - len(self.handles)
        lat = np.asarray(lat)
        self.log(f"requests {len(self.due)} done {lat.size} failed {failed}"
                 f" tiles in window {self.tiles} generator late by at most "
                 f"{self.late!r} s")
        p50, p90 = (np.percentile(lat, [50, 90]) if lat.size
                    else (math.nan, math.nan))
        return {"metrics": {"latency_p50_s": float(p50),
                            "latency_p90_s": float(p90)},
                "attempted": len(self.due), "failed": failed,
                "facts": {"requests": len(self.handles),
                          "tiles": self.tiles}}

    def release(self):
        """Answers to the host; the program's state goes."""
        self.answers = []
        for h, req in zip(self.handles, self.requests):
            if h.status != "done":
                continue
            method, k = req[1], req[2]
            if method == "pcoa":
                a = {"method": method,
                     "eigenvalues": np.asarray(h.result.eigenvalues)}
            else:
                a = {"method": method, "alternative": ALTERNATIVE[method],
                     "permutations": k, "statistic": h.result.statistic,
                     "p_value": h.result.p_value}
            self.answers.append((req, a))
        self.condensed = {sid: np.asarray(self.svc.pool.get(sid).condensed())
                          for sid in self.data
                          if self.svc.pool.get(sid) is not None}
        del self.svc, self.handles

    # -- the output check ----------------------------------------------------
    def check(self, checks, control=None):
        """``checked_requests`` finished requests drawn from the seed: one
        of each method, the largest study's K=999 request among them, the
        rest at random; every draw of each."""
        rng = np.random.default_rng([self.seed, 1])
        idx = list(range(len(self.answers)))
        picked = set()
        for method in self.tr["methods"]:
            mine = [i for i in idx if self.answers[i][0][1] == method]
            if mine:
                picked.add(int(rng.choice(mine)))
        n_max = max(s["samples"] for s in self.cfg["studies"])
        big = [i for i in idx if self.answers[i][0][1] != "pcoa"
               and self.cfg["studies"][self.answers[i][0][0]]["samples"]
               == n_max]
        if big:
            picked.add(int(rng.choice(big)))
        rest = [i for i in idx if i not in picked]
        want = max(self.tr["checked_requests"] - len(picked), 0)
        picked.update(int(i) for i in rng.choice(rest, min(want, len(rest)),
                                                 replace=False))
        prec = R.Precision(control) if control else None
        refs, low = {}, {}

        def ref_of(sid, column, cache, p):
            if (sid, column) not in cache:
                labels, table = self.data[sid]
                cache[sid, column] = R.Reference(
                    R.braycurtis(table, p), labels[column], p)
            return cache[sid, column]

        for i in sorted(picked):
            (si, method, k, key, column), a = self.answers[i]
            sid = self.cfg["studies"][si]["id"]
            sids = [sid] + (self._peers(si) if method in MANTEL else [])
            if method != "partial_mantel":
                sids = sids[:2] if method == "mantel" else sids[:1]
            ref = [ref_of(s, column, refs, R.FLOAT64) for s in sids]
            got = {s: self.condensed[s] for s in sids}
            if prec is not None:
                lref = [ref_of(s, column, low, prec) for s in sids]
                got = {s: R.condensed(r.d) for s, r in zip(sids, lref)}
                a = self._reference_answer(a, method, k, key, lref, prec)
            for s, r in zip(sids, ref):
                checking.distances(checks, got[s], r.d)
            if method == "pcoa":
                top = self.cfg["pcoa"]["dimensions"]
                checking.eigenvalues(checks, a["eigenvalues"],
                                     ref[0].eigenvalues(top), top)
                continue
            ops = self._operands(ref)
            obs, draws = R.test(method, ref[0], ops, key, k)
            checking.permutation_test(checks, a, obs, draws)

    def _operands(self, refs):
        return {"other": refs[1] if len(refs) > 1 else None,
                "control": refs[2] if len(refs) > 2 else None,
                "dimensions": self.cfg["permdisp"]["dimensions"]}

    def _reference_answer(self, a, method, k, key, refs, prec):
        if method == "pcoa":
            return {"method": method, "eigenvalues": refs[0].eigenvalues(
                self.cfg["pcoa"]["dimensions"])}
        ans = checking.reference_answer(method, refs[0],
                                        self._operands(refs), key, k,
                                        ALTERNATIVE[method], prec)
        ans.pop("draws")
        return ans
