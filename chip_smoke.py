#!/usr/bin/env python3
"""Run the analysis battery and the analysis service once on a TPU.

    python chip_smoke.py [--seed 0]              # one chip
    python chip_smoke.py --chips 4 [--seed 0]    # the multi-chip paths only

With one chip it runs, in this one process:

* the library phase — a 4096-sample x 2048-feature abundance table with
  three skewed groups goes through ``Workspace.from_features`` (Bray–
  Curtis) and the whole battery at scikit-bio's default K=999: PCoA,
  PERMANOVA, PERMDISP, ANOSIM, Mantel and partial Mantel. Its results
  are compared with a float64 NumPy/SciPy reference that shares no code
  with the library: sampled rows of distances, every observed
  statistic, the first tile of null draws on the same orders, and the
  PCoA eigenvalues against an exact eigendecomposition (at n=1024);
* the serve phase — three uploaded studies (n=2048) and twelve mixed
  requests through ``AnalysisService``; every request must end ``done``
  with no tile failure, and each result must equal, bitwise, a
  ``Workspace`` run of the same study, method and key.

``--chips 4`` runs only the multi-chip paths and what they are compared
with: the permutation-sharded engine (PERMANOVA, Mantel), the column-
sharded Mantel and the block-sharded centering.

Every table is generated from ``--seed``. Each phase prints its wall
time with compile time kept apart and the device's peak memory; each
check prints its error beside its limit. The last line of standard
output is ``{"ok": true, "device": {...}}``; any failure, or a run
without a TPU, exits non-zero without that line.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

LIBRARY_N, LIBRARY_D = 4096, 2048      # one 16S cohort: m = 8.4M distances
PERMUTATIONS = 999                     # scikit-bio's default
PCOA_CHECK_N = 1024                    # exact float64 eigh runs at this n
DIST_ROWS = 16                         # sampled rows of the distance check
SERVE_N, SERVE_D = 2048, 2048
MULTI_N, MULTI_D, MULTI_K = 2048, 2048, 1000   # K divides over 4 devices

# limits on |library - float64 reference|, scaled as each check says.
# They are fixed from fp32 arithmetic, not from a run on the chip.
DIST_LIMIT = 1e-5       # absolute, Bray–Curtis distances in [0, 1]
STAT_LIMIT = 1e-4       # relative to the statistic's scale in the test
EIG_LIMIT = 1e-3        # relative to the largest eigenvalue
MULTI_LIMIT = 1e-5      # relative, sharded vs one-device null draws

GROUP_SHARES = (0.6, 0.3, 0.1)         # three skewed groups


# --------------------------------------------------------------------------
# bookkeeping
# --------------------------------------------------------------------------
class Phases:
    """Per-phase wall time, the part of it spent compiling, and the
    device's peak memory. Compile time is the sum of JAX's lowering and
    backend-compile events inside the phase."""

    _COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                       "/jax/core/compile/backend_compile_duration")

    def __init__(self, device):
        import jax
        self.device = device
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self._COMPILE_EVENTS:
            self.compile_s += duration

    @contextlib.contextmanager
    def __call__(self, name):
        c0, t0 = self.compile_s, time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        compile_s = self.compile_s - c0
        stats = self.device.memory_stats() or {}
        print(f"phase {name}: wall_s={wall!r} compile_s={compile_s!r} "
              f"run_s={wall - compile_s!r} "
              f"peak_bytes={stats.get('peak_bytes_in_use')}", flush=True)


class Checks:
    """Each check prints its error beside its limit; failures collect."""

    def __init__(self):
        self.failed = []

    def within(self, name, err, limit):
        ok = bool(err <= limit)                  # NaN fails
        print(f"check {name}: error={float(err)!r} limit={limit!r} "
              f"{'ok' if ok else 'FAIL'}", flush=True)
        if not ok:
            self.failed.append(name)

    def true(self, name, ok, detail=""):
        print(f"check {name}: {detail} {'ok' if ok else 'FAIL'}",
              flush=True)
        if not ok:
            self.failed.append(name)


def _rel_err(got, ref, scale=None) -> float:
    """max |got - ref| over ``scale`` (default: the largest |ref|)."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    scale = np.max(np.abs(ref)) if scale is None else scale
    return float(np.max(np.abs(got - ref)) / scale)


# --------------------------------------------------------------------------
# data, made from the seed
# --------------------------------------------------------------------------
def make_groups(rng, n):
    """Labels of three skewed groups (``GROUP_SHARES``), every group
    present."""
    groups = rng.choice(len(GROUP_SHARES), size=n, p=GROUP_SHARES)
    groups[:len(GROUP_SHARES)] = np.arange(len(GROUP_SHARES))
    return groups.astype(np.int32)


def abundance_table(rng, groups, d, like=None):
    """A non-negative (n, d) fp32 table shaped like an ASV table: log-
    normal abundances around a per-group profile, about 70% zeros.
    ``like`` gives a table whose distances correlate with that one's."""
    n = groups.size
    if like is not None:
        noise = rng.lognormal(0.0, 0.5, size=(n, d))
        return (like * noise).astype(np.float32)
    profile = rng.normal(0.0, 1.0, size=(groups.max() + 1, d))
    logab = profile[groups] + rng.normal(0.0, 1.0, size=(n, d))
    present = rng.random((n, d)) < 0.3
    return (np.exp(logab) * present).astype(np.float32)


# --------------------------------------------------------------------------
# the float64 reference (NumPy/SciPy only)
# --------------------------------------------------------------------------
def _pearson(a, b):
    a = a - a.mean()
    b = b - b.mean()
    return float(a @ b / np.sqrt((a @ a) * (b @ b)))


def ref_permanova(d2, ii, jj, codes):
    n, k = codes.size, int(codes.max()) + 1
    sizes = np.bincount(codes, minlength=k)
    ss_total = d2.sum() / n
    same = codes[ii] == codes[jj]
    ss_within = float(np.sum(np.bincount(codes[ii][same], weights=d2[same],
                                         minlength=k) / sizes))
    return ((ss_total - ss_within) / (k - 1)) / (ss_within / (n - k))


def ref_anosim(ranks, ii, jj, codes):
    n = codes.size
    within = codes[ii] == codes[jj]
    return (ranks[~within].mean() - ranks[within].mean()) / (n * (n - 1) / 4)


def ref_permdisp(coords, codes):
    from scipy.stats import f_oneway
    k = int(codes.max()) + 1
    v = np.empty(codes.size)
    for g in range(k):
        mask = codes == g
        v[mask] = np.linalg.norm(coords[mask] - coords[mask].mean(axis=0),
                                 axis=1)
    return float(f_oneway(*(v[codes == g] for g in range(k))).statistic)


def ref_partial_mantel(dx, dy, dz):
    r_xy, r_xz, r_yz = _pearson(dx, dy), _pearson(dx, dz), _pearson(dy, dz)
    return (r_xy - r_xz * r_yz) / np.sqrt((1 - r_xz ** 2) * (1 - r_yz ** 2))


def ref_gower_eigenvalues(x, k):
    from scipy.spatial.distance import pdist, squareform
    d = squareform(pdist(x.astype(np.float64), "braycurtis"))
    e = -0.5 * d * d
    g = e - e.mean(axis=0) - e.mean(axis=1)[:, None] + e.mean()
    return np.sort(np.linalg.eigvalsh(g))[::-1][:k]


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------
def library_phase(phases, checks, seed, n=LIBRARY_N, d=LIBRARY_D,
                  permutations=PERMUTATIONS, pcoa_check_n=PCOA_CHECK_N):
    """The battery through ``Workspace``, checked against float64."""
    from scipy.spatial.distance import cdist, squareform
    from scipy.stats import rankdata

    from repro.api.workspace import Workspace
    from repro.stats import engine

    rng = np.random.default_rng(seed)
    with phases("library.data"):
        groups = make_groups(rng, n)
        x = abundance_table(rng, groups, d)
        y = abundance_table(rng, groups, d, like=x)
        z = abundance_table(rng, groups, d)

    with phases("library.production"):
        ws = Workspace.from_features(x, metric="braycurtis")
        wy = Workspace.from_features(y, metric="braycurtis")
        wz = Workspace.from_features(z, metric="braycurtis")
        for w in (ws, wy, wz):
            w.condensed().block_until_ready()

    res = {}
    with phases("library.pcoa"):
        ordination = ws.pcoa(dimensions=10)
        coords = np.asarray(ordination.coordinates)
    with phases("library.permanova"):
        res["permanova"] = ws.permanova(groups, permutations, key=seed + 1)
    with phases("library.permdisp"):
        res["permdisp"] = ws.permdisp(groups, permutations, key=seed + 2,
                                      dimensions=10)
    with phases("library.anosim"):
        res["anosim"] = ws.anosim(groups, permutations, key=seed + 3)
    with phases("library.mantel"):
        res["mantel"] = ws.mantel(wy, permutations, key=seed + 4)
    with phases("library.partial_mantel"):
        res["partial_mantel"] = ws.partial_mantel(wy, wz, permutations,
                                                  key=seed + 5)
    for method, r in res.items():
        print(f"result {method}: statistic={r.statistic!r} "
              f"p_value={r.p_value!r}", flush=True)

    # the first tile of null draws, through the library's own tile entry
    operands = {"permanova": {"grouping": groups},
                "permdisp": {"grouping": groups, "dimensions": 10},
                "anosim": {"grouping": groups},
                "mantel": {"other": wy},
                "partial_mantel": {"other": wy, "control": wz}}
    b = ws.config.resolve_batch_size(None, 32)
    tiles, orders = {}, {}
    with phases("library.first_tiles"):
        for method, r in res.items():
            stat, _ = ws.statistic(method, **operands[method])
            inv, _ = engine.hoist_and_observe(stat)
            o = engine.permutation_orders(r.key, r.permutations, n)[:b]
            tiles[method] = np.asarray(engine.tile_statistics(stat, inv, o))
            orders[method] = np.asarray(o)

    with phases("library.reference"):
        x64 = x.astype(np.float64)
        rows = np.sort(rng.choice(n, size=min(DIST_ROWS, n), replace=False))
        cols = np.arange(n)
        lo = np.minimum(rows[:, None], cols[None, :])
        hi = np.maximum(rows[:, None], cols[None, :])
        k = lo * (2 * n - lo - 1) // 2 + (hi - lo - 1)
        off = rows[:, None] != cols[None, :]
        dx = np.asarray(ws.condensed()).astype(np.float64)
        dy = np.asarray(wy.condensed()).astype(np.float64)
        dz = np.asarray(wz.condensed()).astype(np.float64)
        got = dx[np.where(off, k, 0)]
        want = cdist(x64[rows], x64, "braycurtis")
        checks.within("distances", np.max(np.abs(got - want)[off]),
                      DIST_LIMIT)

        # each statistic from the library's distances (checked above) and
        # coordinates (checked by the eigenvalue check below)
        ii, jj = np.triu_indices(n, k=1)
        d2 = dx * dx
        ranks = rankdata(dx)
        dx_sq = squareform(dx)
        coords64 = coords.astype(np.float64)

        def reference(method, order):
            g = groups[order]
            if method == "permanova":
                return ref_permanova(d2, ii, jj, g)
            if method == "permdisp":
                return ref_permdisp(coords64, g)
            if method == "anosim":
                return ref_anosim(ranks, ii, jj, g)
            xp = dx_sq[order[ii], order[jj]]
            if method == "mantel":
                return _pearson(xp, dy)
            return ref_partial_mantel(xp, dy, dz)

        # errors are relative to the statistic's scale in this test: the
        # largest |value| among the observed and the tile's null draws
        identity = np.arange(n)
        for method, r in res.items():
            observed = reference(method, identity)
            null = [reference(method, o) for o in orders[method]]
            scale = max(abs(observed), np.max(np.abs(null)))
            checks.within(f"observed.{method}",
                          _rel_err(r.statistic, observed, scale), STAT_LIMIT)
            checks.within(f"first_tile.{method}",
                          _rel_err(tiles[method], null, scale), STAT_LIMIT)

    with phases("library.pcoa_check"):
        xc = x[:pcoa_check_n]
        ev = np.asarray(Workspace.from_features(xc, metric="braycurtis")
                        .pcoa(dimensions=10).eigenvalues)
        want = ref_gower_eigenvalues(xc, ev.size)
        err = np.abs(ev - want) / want[0]
        print(f"pcoa eigenvalues: {ev.tolist()} float64 eigh: "
              f"{want.tolist()}", flush=True)
        # the g-1 between-group axes stand clear of the noise floor; the
        # randomized solver's two power iterations do not resolve the
        # flat tail below them, so only these are held to the limit
        top = len(GROUP_SHARES) - 1
        checks.within(f"pcoa.eigenvalues(n={xc.shape[0]},top={top})",
                      np.max(err[:top]), EIG_LIMIT)


SERVE_REQUESTS = (          # (study, method, K); pcoa takes no K
    ("s0", "permanova", 999), ("s1", "anosim", 499),
    ("s2", "permdisp", 99), ("s0", "mantel", 499),
    ("s1", "partial_mantel", 99), ("s2", "pcoa", None),
    ("s1", "permanova", 99), ("s2", "anosim", 999),
    ("s0", "permdisp", 499), ("s2", "mantel", 999),
    ("s0", "partial_mantel", 999), ("s1", "pcoa", None),
)


def serve_phase(phases, checks, seed, n=SERVE_N, d=SERVE_D):
    """Three studies and mixed requests through ``AnalysisService``; each
    result equal, bitwise, to a ``Workspace`` run on the same chip."""
    from repro.api.workspace import Workspace
    from repro.serve import AnalysisService, ServeConfig

    rng = np.random.default_rng(seed + 100)
    studies = ("s0", "s1", "s2")
    groups = {s: make_groups(rng, n) for s in studies}
    tables = {s: abundance_table(rng, groups[s], d) for s in studies}

    svc = AnalysisService(ServeConfig(timeout_s=None))
    handles = []
    with phases("serve.run"):
        for s in studies:
            svc.upload(s, features=tables[s])
        for i, (s, method, k) in enumerate(SERVE_REQUESTS):
            nxt = studies[(studies.index(s) + 1) % 3]
            after = studies[(studies.index(s) + 2) % 3]
            kw = {"key": seed + i}
            if k is not None:
                kw["permutations"] = k
            if method in ("permanova", "anosim", "permdisp"):
                kw["grouping"] = groups[s]
            if method == "permdisp":
                kw["dimensions"] = 10
            if method in ("mantel", "partial_mantel"):
                kw["other"] = nxt
            if method == "partial_mantel":
                kw["control"] = after
            if method == "pcoa":
                kw["dimensions"] = 10
            handles.append((svc.submit(s, method, **kw), kw))
        svc.run()
    report = svc.report()
    faults = report["faults"]
    print(f"serve faults: {json.dumps(faults, sort_keys=True)}", flush=True)
    print(f"serve tiles_run: {report['scheduler']['tiles_run']}", flush=True)
    statuses = [h.status for h, _ in handles]
    checks.true("serve.all_done", all(s == "done" for s in statuses),
                f"statuses={statuses}")
    clean = (not faults["tile_failures"] and faults["retries"] == 0
             and faults["breaker_trips"] == 0 and faults["degraded"] == 0
             and faults["escalations"] == 0)
    checks.true("serve.no_faults", clean, "no tile failure or retry")

    with phases("serve.workspace_runs"):
        sessions = {s: Workspace.from_features(
            tables[s], config=svc.pool.get(s).config) for s in studies}
        for h, kw in handles:
            if h.status != "done":
                continue
            ws = sessions[h.study_id]
            if h.method == "pcoa":
                want = ws.pcoa(dimensions=10, key=kw["key"]).eigenvalues
                same = np.array_equal(np.asarray(h.result.eigenvalues),
                                      np.asarray(want))
                checks.true(f"serve.{h.request_id}.pcoa", same,
                            "eigenvalues bitwise equal to Workspace")
                continue
            args = {k: v for k, v in kw.items() if k not in ("other",
                                                             "control")}
            if "other" in kw:
                args["other"] = sessions[kw["other"]]
            if "control" in kw:
                args["control"] = sessions[kw["control"]]
            want = getattr(ws, h.method)(**args)
            checks.true(f"serve.{h.request_id}.{h.method}",
                        h.result.p_value == want.p_value,
                        f"p={h.result.p_value!r} workspace="
                        f"{want.p_value!r}")


def multichip_phase(phases, checks, seed, n=MULTI_N, d=MULTI_D,
                    permutations=MULTI_K):
    """The sharded paths on four devices, each against one device."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.api.workspace import Workspace
    from repro.core.centering import (center_distance_matrix,
                                      center_distance_matrix_distributed)
    from repro.core.mantel import MantelStatistic, mantel_null_distributed
    from repro.stats import engine

    devices = jax.devices()
    if len(devices) != 4:
        raise RuntimeError(f"the multi-chip phase needs 4 devices, JAX "
                           f"found {len(devices)}")
    rng = np.random.default_rng(seed + 200)
    groups = make_groups(rng, n)
    x = abundance_table(rng, groups, d)
    y = abundance_table(rng, groups, d, like=x)
    ws = Workspace.from_features(x, metric="braycurtis")
    wy = Workspace.from_features(y, metric="braycurtis")
    auto = jax.sharding.AxisType.Auto
    line = jax.make_mesh((4,), ("data",), devices=devices,
                         axis_types=(auto,))
    square = jax.make_mesh((2, 2), ("data", "model"), devices=devices,
                           axis_types=(auto, auto))

    def holders(arr):
        return sorted({s.device.id for s in arr.addressable_shards})

    def scale(observed, null):          # as in the library phase
        return max(abs(float(observed)), float(np.max(np.abs(null))))

    def one_device(stat, key, n_perm_devices):
        """The same draws on one device: per_perm over each device's
        ``fold_in(key, dev)`` orders, in device order."""
        per_dev = permutations // n_perm_devices
        f = jax.jit(lambda inv, orders: jax.lax.map(
            lambda o: stat.per_perm(inv, o), orders, batch_size=8))
        inv, _ = engine.hoist_and_observe(stat)
        return np.concatenate([np.asarray(f(inv, engine.permutation_orders(
            jax.random.fold_in(key, dev), per_dev, n)))
            for dev in range(n_perm_devices)])

    for method, operands in (("permanova", {"grouping": groups}),
                             ("mantel", {"other": wy})):
        stat, _ = ws.statistic(method, **operands)
        key = jax.random.PRNGKey(seed + 10)
        with phases(f"multichip.{method}"):
            observed, null = engine.null_distribution_distributed(
                stat, line, permutations, key)
            null.block_until_ready()
        checks.true(f"multichip.{method}.devices", len(holders(null)) == 4,
                    f"null {null.sharding} on devices {holders(null)}")
        want = one_device(stat, key, 4)
        checks.within(f"multichip.{method}.per_device_draws",
                      _rel_err(null, want, scale(observed, want)),
                      MULTI_LIMIT)

    key = jax.random.PRNGKey(seed + 11)
    with phases("multichip.mantel_distributed"):
        observed, null = mantel_null_distributed(ws.dm, wy.dm, square,
                                                 permutations, key)
        null.block_until_ready()
    checks.true("multichip.mantel_distributed.devices",
                len(holders(null)) == 4,
                f"null {null.sharding} on devices {holders(null)}")
    stat = MantelStatistic(ws.dm.data, wy.dm.data, n)     # the same hoist
    want = one_device(stat, key, 2)
    checks.within("multichip.mantel_distributed.per_device_draws",
                  _rel_err(null, want, scale(observed, want)), MULTI_LIMIT)

    with phases("multichip.centering"):
        dm = jax.device_put(ws.dm.data, NamedSharding(square,
                                                      P("data", "model")))
        got = center_distance_matrix_distributed(dm, square)
        got.block_until_ready()
    checks.true("multichip.centering.devices", len(holders(got)) == 4,
                f"centered {got.sharding} on devices {holders(got)}")
    want = center_distance_matrix(ws.dm.data)
    checks.within("multichip.centering.vs_one_device",
                  _rel_err(got, want), MULTI_LIMIT)
    for dev in devices:
        stats = dev.memory_stats()
        if stats is None:               # the host backend keeps none
            print(f"device {dev.id}: memory_stats not reported", flush=True)
            continue
        print(f"device {dev.id}: peak_bytes={stats.get('peak_bytes_in_use')}"
              f" bytes_in_use={stats.get('bytes_in_use')}", flush=True)
        checks.true(f"multichip.device{dev.id}.holds_work",
                    stats.get("peak_bytes_in_use", 0) > 0,
                    "peak_bytes_in_use > 0")


# --------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip paths")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices; JAX found {len(devices)}", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.cache import enable_compile_cache
    print(f"jax {jax.__version__} | {dev.platform} {dev.device_kind} x"
          f"{len(devices)} | compile cache {enable_compile_cache()}",
          flush=True)

    phases, checks = Phases(dev), Checks()
    with phases("total"):
        if args.chips == 4:
            multichip_phase(phases, checks, args.seed)
        else:
            library_phase(phases, checks, args.seed)
            serve_phase(phases, checks, args.seed)
    if checks.failed:
        print(f"chip_smoke: failed checks: {checks.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
