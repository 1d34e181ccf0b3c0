"""Benchmark harness — one section per paper table (deliverable (d)).

``PYTHONPATH=src python -m benchmarks.run [--fast|--smoke]
[--suite paper|stats|pcoa|api|dist]``

Suites:
  paper (default) — the paper's tables:
    Table 1 — centering (original vs fused)
    Table 2 — mantel (original vs hoisted+fused)
    Table 3 — validation (original vs fused)
    §4.1    — pcoa end-to-end + validation caching
    summary — measured speedups vs the paper's claimed ranges
  stats — the repro.stats subsystem (PERMANOVA / ANOSIM / partial Mantel,
    ref vs fused at n ∈ {512, 2048}, K=999); writes BENCH_stats.json.
  pcoa — ordination: ref/fused materialize-then-solve vs the matrix-free
    operator path at n ∈ {2048, 4096}; writes BENCH_pcoa.json with wall
    time and peak matrix bytes.
  api — hoist-once sessions: analytic O(n²)-pass counts (bytes of D read)
    for the 4-analysis study battery, one shared Workspace vs standalone
    per-call hoists; writes BENCH_api.json. The gate is the analytic
    traffic ratio, not wall-clock (container timing is ±40% noisy).
  dist — feature-table sessions: the fused repro.dist condensed
    production (Workspace.from_features, square-free) vs the
    materialize-then-analyze baseline at n ∈ {2048, 4096}; writes
    BENCH_dist.json with the analytic n×n bytes avoided.
  mantel — the condensed batch-fused permutation loop: analytic
    per-permutation bytes moved (square-gather loop vs condensed
    batch-fused, at n ∈ {2048, 4096}, K=999); writes BENCH_mantel.json.
    Acceptance gate: ≥ 8x less traffic than the square-gather loop.
  tune — the repro.tune solver: modeled effective traffic of
    solver-chosen tiles vs the hand-picked constants, across every
    suite's workload at n ∈ {2048, 4096}; writes BENCH_tune.json plus
    the container's calibration profile (tune_profile.json). Gate:
    tuned never models worse than the constants.
  serve — the repro.serve front door: R concurrent mixed-K mantel
    requests against one pooled study, gated on the coalescing bound
    (tiles == ceil(ΣK/B)), hoists charged once per study, and the
    session ledger's perm traffic matching perm_traffic_floats; writes
    BENCH_serve.json at n ∈ {512, 2048}, with the chaos sweep's
    receipts in its "chaos" section. With --chaos, runs ONLY the
    seeded fault-injection soak (repro.faults): all requests must
    terminate, completed p-values must be bitwise-equal to the
    fault-free run, retry amplification stays capped, and journal
    recovery runs exactly the remaining tiles with zero re-hoists.

``--smoke`` runs the dist + api + mantel suites at tiny sizes with NO
BENCH artifact written — the CI guard that the benchmark entry points
can't silently rot (exercises the same code paths; the tracked
BENCH_*.json files are only ever written by full-size runs). It then
runs the full 6-analysis battery on an observability-enabled
feature-backed Workspace under the recompile sentinel — the padded
``per_batch`` path must compile exactly ONE ``kernels.permute_reduce``
program per invariant-stack shape across different K values — and
writes the session's ``RunReport`` JSON (``--report``, default
``RunReport_smoke.json``; CI uploads it as a workflow artifact).

Every suite (and the smoke) finishes through the perf-trajectory gate
(``benchmarks/trajectory.py``): its analytic ratios — plus, in smoke,
the ``obs.probe`` compile-time byte measurements — append to
``BENCH_trajectory.jsonl`` and are compared against the committed
``benchmarks/trajectory_baseline.json``; a regression past tolerance
exits nonzero. Wall-clock never gates (±40% container noise).
"""

import argparse
import platform

import jax

from benchmarks import bench_api, bench_center, bench_dist, bench_mantel, \
    bench_pcoa, bench_serve, bench_stats, bench_tune, bench_validation, \
    trajectory
from repro.launch.cache import enable_compile_cache


def _smoke_report(path: str) -> None:
    """The observability acceptance battery: every analysis spanned,
    every hoist/batch charged, the recompile sentinel gating."""
    import numpy as np

    from repro.api.config import ExecConfig
    from repro.api.workspace import Workspace
    from repro.obs import ObsConfig, sentinel

    rng = np.random.default_rng(0)
    cfg = ExecConfig(obs=ObsConfig(enabled=True))
    ws = Workspace.from_features(rng.random((64, 16), dtype=np.float32) + .01,
                                 config=cfg)
    wsy = Workspace.from_features(rng.random((64, 16), dtype=np.float32) + .01,
                                  config=cfg)
    wsz = Workspace.from_features(rng.random((64, 16), dtype=np.float32) + .01,
                                  config=cfg)
    grouping = rng.integers(0, 4, 64)

    # the gate: the battery below runs the batched condensed loop for
    # three statistics (Mantel S=1 / ANOSIM S=1 — same program — and
    # partial Mantel S=2) at TWO different K values each path; more than
    # 2 distinct kernels.permute_reduce programs means a shape leaked
    # back into the trace signature (the pre-PR-5 trailing-block bug)
    with sentinel.expect("kernels.permute_reduce", max_programs=2):
        ws.pcoa(dimensions=8)
        ws.permanova(grouping, permutations=49)
        ws.permdisp(grouping, permutations=49, dimensions=8)
        ws.anosim(grouping, permutations=49)
        ws.mantel(wsy, permutations=49)
        ws.mantel(wsy, permutations=17)      # second K: same program
        ws.partial_mantel(wsy, wsz, permutations=49)

    report = ws.report(meta={"suite": "smoke"})
    report.save(path)
    led = report.ledger
    print(f"\n# smoke RunReport -> {path}")
    print(f"#   hoist passes {led['hoist_passes']:.1f}  "
          f"total {led['total_bytes'] / 1e6:.2f} MB analytic  "
          f"ops {sorted(led['by_op'])}")
    programs = {k: v["programs"] for k, v in report.compile.items()}
    print(f"#   compile window: {programs}  program prep "
          f"{report.prep.get('seconds', 0.0):.2f} s")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller sizes / fewer repeats")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: dist+api+mantel at tiny sizes (no "
                         "BENCH artifacts) + the obs-instrumented battery "
                         "under the recompile sentinel")
    ap.add_argument("--report", default="RunReport_smoke.json",
                    help="where --smoke writes the RunReport JSON "
                         "(uploaded by CI as a workflow artifact)")
    ap.add_argument("--chaos", action="store_true",
                    help="with --suite serve: run ONLY the seeded "
                         "chaos-soak sweep (bounded seeds, no BENCH "
                         "artifacts) — gates on termination, bitwise-"
                         "equal completed p-values, retry amplification, "
                         "and journal-recovery tile counts; never "
                         "wall-clock")
    ap.add_argument("--suite", default="paper",
                    choices=("paper", "stats", "pcoa", "api", "dist",
                             "mantel", "tune", "serve"),
                    help="paper tables (default), the repro.stats sweep, "
                         "the matrix-free ordination sweep, the hoist-once "
                         "Workspace session accounting, the fused "
                         "feature-table distance production, the "
                         "condensed Mantel permutation-traffic accounting, "
                         "the repro.tune solved-vs-default tile pricing, "
                         "or the repro.serve coalescing gates")
    args, _ = ap.parse_known_args()
    enable_compile_cache()

    print(f"# repro benchmarks — {platform.processor() or 'cpu'} · "
          f"jax {jax.__version__} · devices={jax.device_count()}")
    print("# paper: Sfiligoi/McDonald/Knight PEARC'21 — sizes scaled to "
          "one CPU core; the measured quantity is the fused-vs-multipass "
          "RATIO (see EXPERIMENTS.md §Benchmarks)")

    if args.smoke:
        smoke = {}
        smoke["dist"] = bench_dist.run(sizes=(128, 256), d=32,
                                       permutations=49, out_json=None)
        smoke["api"] = bench_api.run(sizes=(128,), permutations=49,
                                     out_json=None)
        smoke["mantel"] = bench_mantel.run_suite(sizes=(64,),
                                                 permutations=19, batch=8,
                                                 out_json=None)
        # the tune gate: solver tiles never price worse than the
        # hand-picked constants in the analytic model (asserted inside)
        smoke["tune"] = bench_tune.run(sizes=(64, 256), d=32,
                                       out_json=None, profile_json=None)
        # the serve gates: coalesced tiles == ceil(ΣK/B), hoists once
        # per study, ledger traffic == the audited model (asserted
        # inside bench_serve._workload)
        smoke["serve"] = bench_serve.run(sizes=(64,), permutations=99,
                                         batch=16, requests=6,
                                         out_json=None, chaos=False)
        _smoke_report(args.report)
        # the perf-trajectory gate: every suite's analytic ratios plus
        # the compile-time probe measurements, appended to the JSONL
        # ledger and compared against the committed baseline. A
        # regression past tolerance exits nonzero (wall-clock is never
        # gated — see benchmarks/trajectory.py).
        metrics = {}
        for suite, results in smoke.items():
            metrics.update(trajectory.flatten(suite, results))
        metrics.update(trajectory.probe_metrics())
        trajectory.check("smoke", metrics)
        print("\n# smoke OK — dist + api + mantel + tune + serve suites "
              "ran end-to-end (no BENCH artifacts written) + obs battery "
              "passed the recompile gate + trajectory gate green")
        return

    if args.suite == "tune":
        if args.fast:
            # separate artifact: fast-mode numbers must not clobber the
            # tracked full-size trajectory file
            s = bench_tune.run(sizes=(256, 512), d=64,
                               out_json="BENCH_tune_fast.json",
                               profile_json="tune_profile.json")
        else:
            s = bench_tune.run()
        print("\n# summary — modeled effective traffic, default / tuned")
        for n, r in s.items():
            worst = min(o["ratio"] for su in r["suites"].values()
                        for o in su.values())
            print(f"tune            n={n:<6d} worst suite ratio "
                  f"{worst:6.2f}x (>= 1.00 required)")
        trajectory.check("tune", s)
        return

    if args.suite == "serve":
        if args.chaos:
            # the chaos-soak job: every gate is asserted inside
            # run_chaos (termination, bitwise-equal completed results,
            # amplification cap, recovery tile counts) — reaching the
            # summary print IS the pass
            c = bench_serve.run_chaos()
            bench_serve.print_chaos(c)
            print("\n# chaos OK — all requests terminated under every "
                  "seed, completed p-values bitwise-equal to the "
                  "fault-free run, amplification bounded, recovery "
                  "resumed without re-hoisting")
            return
        if args.fast:
            # separate artifact: fast-mode numbers must not clobber the
            # tracked full-size trajectory file
            # chaos is skipped here: the dedicated --chaos CI job owns
            # the soak, and fast mode should stay fast
            s = bench_serve.run(sizes=(128, 256), permutations=199,
                                batch=16, requests=8,
                                out_json="BENCH_serve_fast.json",
                                chaos=False)
        else:
            s = bench_serve.run()
        print("\n# summary — coalesced serving vs per-request tiles "
              "(ledger-verified)")
        for n, r in s.items():
            if not isinstance(n, int):     # the chaos receipts
                continue
            print(f"serve           n={n:<6d} {r['tile_ratio']:6.2f}x "
                  f"fewer tiles, {r['traffic_ratio']:6.2f}x less perm "
                  f"traffic, hoists once per study")
        trajectory.check("serve", s)
        return

    if args.suite == "mantel":
        if args.fast:
            # separate artifact: fast-mode numbers must not clobber the
            # tracked full-size trajectory file
            s = bench_mantel.run_suite(sizes=(256, 512), permutations=99,
                                       out_json="BENCH_mantel_fast.json")
        else:
            s = bench_mantel.run_suite()
        print("\n# summary — per-permutation traffic, square-gather / "
              "condensed batch-fused (analytic)")
        for n, r in s.items():
            print(f"mantel-traffic  n={n:<6d} "
                  f"{r['ratio_vs_square_gather']:6.2f}x less traffic "
                  f"({r['ratio_vs_original']:.2f}x vs eager original)")
        trajectory.check("mantel", s)
        return

    if args.suite == "dist":
        if args.fast:
            # separate artifact: fast-mode numbers must not clobber the
            # tracked full-size trajectory file
            s = bench_dist.run(sizes=(256, 512), d=64, permutations=99,
                               out_json="BENCH_dist_fast.json")
        else:
            s = bench_dist.run()
        print("\n# summary — n×n bytes avoided, fused / materialized")
        for n, r in s.items():
            print(f"dist-session    n={n:<6d} {r['bytes_avoided'] / 1e6:8.1f}"
                  f" MB avoided ({r['peak_ratio']:.2f}x peak matrix bytes,"
                  f" {r['traffic_ratio']:.2f}x hoist traffic, analytic)")
        trajectory.check("dist", s)
        return

    if args.suite == "api":
        if args.fast:
            # separate artifact: fast-mode numbers must not clobber the
            # tracked full-size trajectory file
            s = bench_api.run(sizes=(256, 512), permutations=199,
                              out_json="BENCH_api_fast.json")
        else:
            s = bench_api.run()
        print("\n# summary — O(n²) traffic, standalone / one Workspace")
        for n, r in s.items():
            print(f"api-session     n={n:<6d} {r['traffic_ratio']:6.2f}x "
                  f"less matrix traffic (analytic)")
        trajectory.check("api", s)
        return

    if args.suite == "pcoa":
        if args.fast:
            # separate artifact: fast-mode numbers must not clobber the
            # tracked full-size trajectory file
            s = bench_pcoa.run_suite(sizes=(512, 1024),
                                     out_json="BENCH_pcoa_fast.json")
        else:
            s = bench_pcoa.run_suite()
        print("\n# summary — matrix-free vs materialize-then-solve (fused)")
        for n, per_impl in s.items():
            mf = per_impl["matrix-free"]
            print(f"pcoa            n={n:<6d} {mf['speedup_vs_fused']:6.2f}x "
                  f"wall, {mf['matrix_bytes_vs_fused']:.2f}x matrix bytes")
        return

    if args.suite == "stats":
        if args.fast:
            # separate artifact: fast-mode numbers must not clobber the
            # tracked full-size (n=2048, K=999) trajectory file
            s = bench_stats.run(sizes=(256, 512), permutations=199,
                                out_json="BENCH_stats_fast.json")
        else:
            s = bench_stats.run()
        print("\n# summary — speedup (original / fused), repro.stats engine")
        for n, per_stat in s.items():
            for name, r in per_stat.items():
                print(f"{name:15s} n={n:<6d} {r['speedup']:6.1f}x")
        return

    if args.fast:
        c = bench_center.run(sizes=(2048, 4096))
        m = bench_mantel.run(sizes=(256, 512), permutations=49)
        v = bench_validation.run(sizes=(2048, 4096))
        p = bench_pcoa.run(sizes=(1024,))
    else:
        c = bench_center.run()
        m = bench_mantel.run()
        v = bench_validation.run()
        p = bench_pcoa.run()

    print("\n# summary — speedup (original / optimized) vs the paper's")
    print("# SINGLE-CORE rows (this container is 1 core; the paper's")
    print("# headline 10-200x additionally includes its multicore scaling,")
    print("# reproduced here structurally by the shard_map paths)")
    biggest = max(k for k in c if isinstance(k, int))
    print(f"centering   {c[biggest]['original'] / c[biggest]['fused']:6.1f}x"
          f"   [paper Table 1, 1 core: 2.0-3.3x; 16 cores: 24-30x]")
    biggest = max(k for k in m if isinstance(k, int))
    print(f"mantel      {m[biggest]['original'] / m[biggest]['fused']:6.1f}x"
          f"   [paper Table 2, 1 core: 14.5-24.7x; 16 cores: 90-162x]")
    biggest = max(k for k in v if isinstance(k, int))
    print(f"validation  {v[biggest]['original'] / v[biggest]['fused']:6.1f}x"
          f"   [paper Table 3, 1 core: 0.7-2.8x; 16 cores: 4.5-39x]")
    vc = p["validation_caching"]
    print(f"valid-cache {vc['revalidate'] / vc['copy']:6.1f}x"
          f"   [paper §4.3: 'avoid unnecessary validations']")


if __name__ == "__main__":
    main()
