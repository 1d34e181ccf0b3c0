"""Serving launcher: the ``repro.serve`` analysis front door, end to end.

``python -m repro.launch.serve --smoke``

Drives an ``AnalysisService`` through a synthetic multi-tenant workload:
several studies are uploaded, a mixed bag of concurrent requests (the
full battery — pcoa, permanova, anosim, permdisp, mantel,
partial_mantel — at mixed per-request K) is submitted, the coalescing
tile loop drains them, and the ``serve_report()`` summary prints.

This replaces the old token-decoding continuous-batching demo, which was
dead code with a real bug: its slot-refill path popped the queued prompt
and appended a fresh slot WITHOUT running a prefill, so a "refilled"
request decoded against the previous occupant's stale KV cache. The
permutation-tile scheduler keeps the idiom that demo was after — a
finished request's tile rows are refilled from the queue on the very
next tile — with the refill done correctly by construction: every row
carries its own permutation order, so there is no per-slot state to
forget to reset.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from repro.launch.cache import enable_compile_cache
from repro.serve import AnalysisService, ServeConfig


def run(args) -> dict:
    """Drive the service; returns ``serve_report()`` plus ``statuses``,
    each request's final status in submission order."""
    rng = np.random.default_rng(args.seed)
    svc = AnalysisService(ServeConfig(batch_size=args.batch,
                                      timeout_s=None,
                                      max_sessions=max(4, args.studies)))

    # uploads: half feature-backed, half square-backed
    study_ids = [f"study{i}" for i in range(args.studies)]
    for i, sid in enumerate(study_ids):
        feats = rng.random((args.n, 8)).astype(np.float32)
        if i % 2:
            from repro.api.workspace import Workspace
            dm = np.asarray(
                Workspace.from_features(feats).dm.data)
            svc.upload(sid, dm)
        else:
            svc.upload(sid, features=feats)

    grouping = np.arange(args.n) % 3
    methods = ("permanova", "anosim", "permdisp", "mantel",
               "partial_mantel", "pcoa")
    handles = []
    for r in range(args.requests):
        sid = study_ids[r % len(study_ids)]
        method = methods[r % len(methods)]
        kw = {"permutations": args.permutations // (1 + r % 3),
              "key": r}
        if method in ("permanova", "anosim", "permdisp"):
            kw["grouping"] = grouping
        if method in ("mantel", "partial_mantel"):
            kw["other"] = study_ids[(r + 1) % len(study_ids)]
        if method == "partial_mantel":
            kw["control"] = study_ids[(r + 2) % len(study_ids)]
        if method == "pcoa":
            kw = {"dimensions": 3}
        handles.append(svc.submit(sid, method, **kw))

    svc.run()
    report = svc.report()
    ok = sum(h.status == "done" for h in handles)
    g = report["gauges"]
    print(f"[serve] {ok}/{len(handles)} requests done | "
          f"{report['scheduler']['tiles_run']} tiles of B={args.batch} | "
          f"{report['pool']['sessions']} sessions, "
          f"{report['pool']['nbytes']} hoist bytes resident | "
          f"throughput {g['throughput_rps']:.1f} req/s")
    for h in handles[: args.show]:
        print(f"  {h.request_id:>4} {h.method:<14} {h.status:<8}"
              + (f" p={h.result.p_value:.4f}"
                 if getattr(h.result, "p_value", None) is not None else ""))
    if args.json:
        print(json.dumps({"gauges": g, "pool": report["pool"],
                          "scheduler": report["scheduler"]}, indent=2,
                         default=str))
    report["statuses"] = [h.status for h in handles]
    return report


def main(argv=None) -> int:
    """Returns 0 only when every request ended ``done``."""
    ap = argparse.ArgumentParser(
        description="drive the repro.serve analysis front door")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes (CI-friendly)")
    ap.add_argument("--studies", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--n", type=int, default=128)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--permutations", type=int, default=999)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--show", type=int, default=12,
                    help="per-request lines to print")
    ap.add_argument("--json", action="store_true",
                    help="dump the gauge/pool/scheduler sections as JSON")
    args = ap.parse_args(argv)
    if args.smoke:
        args.n = min(args.n, 32)
        args.permutations = min(args.permutations, 99)
    enable_compile_cache()
    statuses = run(args)["statuses"]
    failed = [s for s in statuses if s != "done"]
    if failed:
        print(f"[serve] {len(failed)} of {len(statuses)} requests did not "
              f"finish: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
