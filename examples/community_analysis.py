"""Community ecology walkthrough — one hoist-once Workspace session,
opened straight from the raw feature table.

The paper's motivating workload (§1) is microbiome beta-diversity:
compute distance matrices, then ask statistical questions of them. This
example runs the full battery on one simulated study — the
personal-device-scale analysis of Sfiligoi et al. 2021:

    samples from 4 "treatment" groups, two measurements + one confounder
      → PCoA        where do the samples sit?    (matrix-free ordination)
      → PERMANOVA   do group centroids differ?        (pseudo-F)
      → PERMDISP    ...or is it just unequal spread?  (dispersion F)
      → ANOSIM      do within < between distances?    (Clarke's R)
      → Mantel      do the two measurements agree?    (Pearson r)
      → partial Mantel   ...controlling for the confounding gradient?

Everything runs through ``repro.api.Workspace`` — and since the
``repro.dist`` subsystem, the session starts one step earlier than a
distance matrix: ``Workspace.from_features`` turns the (n, d) sample
table into CONDENSED distances tile-by-tile, accumulating the operator
means during the same sweep — and since the condensed batch-fused
permutation loop (``kernels.permute_reduce``), ALL SEVEN analyses below
complete without an n×n matrix of any kind ever existing: the Mantel
family and ANOSIM gather condensed storage by closed-form triangle
indexing (~11x less per-permutation traffic than the old square-gather
loop — the audited analytic accounting is ``BENCH_mantel.json``, via
``benchmarks/run.py --suite mantel``). The shared hoists are computed on
first use and reused by every later test; one ``ExecConfig`` carries
every execution knob; every result records its RNG key.

The primary session runs with **observability on**
(``ExecConfig(obs=ObsConfig(enabled=True))``): every analysis and hoist
is a timed span, every build/batch is charged to the analytic traffic
ledger, and the run ends by printing the span tree and the ledger
totals — the same ``RunReport`` document CI archives from ``--smoke``.

    PYTHONPATH=src python examples/community_analysis.py [--n 2048]

Legacy style (still supported — each call is a thin wrapper over a
one-shot Workspace, identical p-values per key, but the hoists are NOT
shared across calls, and you build the square matrix yourself):

    from scipy.spatial.distance import pdist, squareform   # or repro.dist
    from repro.core import DistanceMatrix, mantel, pcoa
    from repro.stats import anosim, partial_mantel, permanova, permdisp
    metric_a = DistanceMatrix(squareform(pdist(table)))
    ord_ = pcoa(metric_a, dimensions=3)
    r = permanova(metric_a, grouping, 999, key)      # re-centers
    r = permdisp(metric_a, grouping, 999, key)       # re-ordinates
    r = anosim(metric_a, grouping, 999, key)         # re-ranks
    s, p, _ = mantel(metric_a, metric_b, 999, key)   # re-normalizes
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import ExecConfig, Workspace
from repro.obs import ObsConfig


def simulate_study(key, n, num_groups=4, dim=8):
    """Two community measurements + a confounding environmental gradient.

    Sample i sits at (group centroid) + (gradient effect) + noise; table B
    is table A re-measured with noise, and the gradient alone drives the
    confounder — so partial Mantel should keep A~B strong while a naive
    Mantel of A vs the gradient is spurious. Returned as raw (n, d)
    feature tables: building the distances is part of the workload now.
    """
    k_grp, k_grad, k_a, k_b = jax.random.split(key, 4)
    grouping = np.arange(n) % num_groups
    centroids = 2.0 * jax.random.normal(k_grp, (num_groups, dim))
    gradient = jax.random.normal(k_grad, (n, 1))           # e.g. pH
    table_a = (centroids[grouping]
               + 1.5 * gradient * jnp.ones((1, dim))
               + jax.random.normal(k_a, (n, dim)))
    table_b = table_a + 0.3 * jax.random.normal(k_b, (n, dim))
    return grouping, table_a, table_b, gradient


def main(n: int = 2048, permutations: int = 999):
    key = jax.random.PRNGKey(0)
    grouping, table_a, table_b, gradient = simulate_study(key, n)
    test_key = 1                     # int seeds and PRNG keys both accepted
    print(f"== community analysis: {n} samples, 4 groups, K={permutations} ==")

    # one session per measurement: the feature table is validated finite +
    # canonicalized once, distances are produced condensed with the
    # operator means fused into the sweep. ExecConfig is where execution
    # knobs go (metric=..., block=..., batch_size=..., a mesh for the
    # distributed paths, ...) — defaults suit one CPU/TPU.
    # obs=ObsConfig(enabled=True) turns the primary session's telemetry
    # on: spans + analytic traffic ledger (off by default: zero overhead).
    ws = Workspace.from_features(table_a, metric="euclidean",
                                 config=ExecConfig(
                                     obs=ObsConfig(enabled=True)))
    ws_b = Workspace.from_features(table_b, metric="euclidean")
    ws_env = Workspace.from_features(gradient, metric="euclidean")

    t0 = time.perf_counter()
    ord_ = ws.pcoa(dimensions=3)                 # matrix-free by default
    jax.block_until_ready(ord_.coordinates)
    pe = np.asarray(ord_.proportion_explained)
    print(f"[0] PCoA (matrix-free)  top-3 axes explain "
          f"{100 * pe.sum():.1f}% of inertia "
          f"({time.perf_counter() - t0:.2f}s, no n² intermediate)")

    t0 = time.perf_counter()
    r = ws.permanova(grouping, permutations, test_key)
    print(f"[1] PERMANOVA      F={r.statistic:8.3f}  p={r.p_value:.4f}  "
          f"({time.perf_counter() - t0:.2f}s)")

    t0 = time.perf_counter()
    r = ws.permdisp(grouping, permutations, test_key, dimensions=3)
    print(f"[2] PERMDISP       F={r.statistic:8.3f}  p={r.p_value:.4f}  "
          f"({time.perf_counter() - t0:.2f}s) — reused [0]'s ordination")

    t0 = time.perf_counter()
    r = ws.anosim(grouping, permutations, test_key)
    print(f"[3] ANOSIM         R={r.statistic:8.3f}  p={r.p_value:.4f}  "
          f"({time.perf_counter() - t0:.2f}s)")

    assert "square" not in ws.cache
    print(f"    -- four analyses done, no n×n matrix of any kind ever "
          f"existed (even ANOSIM's ranks stayed condensed; cached: "
          f"{sorted(k if isinstance(k, str) else k[0] for k in ws.cache.keys())})")

    t0 = time.perf_counter()
    r = ws.mantel(ws_b, permutations, test_key)
    print(f"[4] Mantel A~B     r={r.statistic:8.3f}  p={r.p_value:.4f}  "
          f"({time.perf_counter() - t0:.2f}s) — condensed batch loop, "
          f"square built: {'square' in ws.cache}")

    t0 = time.perf_counter()
    r = ws.mantel(ws_env, permutations, test_key)
    print(f"[5] Mantel A~env   r={r.statistic:8.3f}  p={r.p_value:.4f}  "
          f"({time.perf_counter() - t0:.2f}s) — the confounded read")

    t0 = time.perf_counter()
    r = ws.partial_mantel(ws_b, ws_env, permutations, test_key)
    print(f"[6] partial A~B|env r={r.statistic:7.3f}  p={r.p_value:.4f}  "
          f"({time.perf_counter() - t0:.2f}s) — agreement survives the "
          f"control")

    # the whole seven-analysis study ran square-free, in every session
    for w in (ws, ws_b, ws_env):
        assert "square" not in w.cache and w._dm is None

    families = {k if isinstance(k, str) else k[0] for k in ws.cache.misses}
    builds = {a: ws.cache.build_count(a) for a in sorted(families)}
    print(f"== analysis complete — hoists built once each: {builds}, "
          f"cache hits: {sum(ws.cache.hits.values())} ==")

    # -- the observability readout: where the time and the bytes went ----
    # (the same data ws.report() serializes as a RunReport JSON document)
    print("\n== span tree (primary session; wall seconds) ==")
    for line in ws.obs.tracer.tree_lines():
        print("  " + line)
    report = ws.report(meta={"example": "community_analysis"})
    led = report.ledger
    print(f"== analytic traffic ledger: {led['hoist_passes']:.1f} n²-pass "
          f"equivalents of hoist traffic, {led['total_bytes'] / 1e6:.1f} MB "
          f"total analytic ==")
    for op, v in sorted(led["by_op"].items()):
        print(f"   {op:22s} {v['bytes'] / 1e6:10.2f} MB  x{v['count']}")
    print(f"== recompile window: "
          f"{ {k: v['programs'] for k, v in report.compile.items()} } "
          f"(one kernels.permute_reduce program per invariant-stack "
          f"shape, whatever K) ==")

    # -- measured vs modeled: the compiled programs' actual byte counts
    # (obs.probe, ahead-of-time compile, scan-corrected) reconciled
    # against the analytic envelope (obs.drift)
    if report.measured:
        print("\n== measured (AOT probes, scan-corrected bytes) ==")
        for name, rec in sorted(report.measured.items()):
            print(f"   {name:26s} {rec['bytes_corrected'] / 1e6:10.2f} MB "
                  f"moved, peak {rec['peak_bytes'] / 1e6:8.2f} MB")
        print(f"== drift verdicts (measured inside the modeled "
              f"envelope?) ==")
        for v in report.drift["verdicts"]:
            print(f"   {v['name']:26s} {v['quantity']:5s} "
                  f"{v['measured'] / 1e6:10.2f} MB in "
                  f"[{v['expected_lo'] / 1e6:.2f}, "
                  f"{v['expected_hi'] / 1e6:.2f}] "
                  f"{'OK' if v['within'] else 'DRIFT'}  ({v['regime']})")
        verdict = ("within tolerance" if report.drift_ok
                   else "DRIFT DETECTED")
        print(f"== drift: {verdict} on backend "
              f"{report.drift['backend']} ==")
    return r


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--permutations", type=int, default=999)
    a = ap.parse_args()
    main(a.n, a.permutations)
