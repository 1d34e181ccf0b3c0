"""repro.tune tests: ledger/model parity (the published BENCH numbers
reproduced from the cost model), solver properties (fits-the-budget,
monotone traffic, K-independence, the int32 triangle guard, shrink-only
feature_block), the ExecConfig auto plumbing, and the acceptance
battery — an ``ExecConfig(auto=True)`` session must be bitwise-identical
per key to the default-config run while never modeling more traffic.
"""

import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.api.config import ExecConfig
from repro.api.workspace import Workspace
from repro.core.distance_matrix import MAX_TRIANGLE_N, random_distance_matrix
from repro.core.mantel import MantelStatistic
from repro.obs import sentinel
from repro.obs.ledger import (HOIST_PASSES, perm_traffic_floats,
                              production_floats)
from repro.stats import permutation_test
from repro.tune import (BackendBudget, calibrate, detect_budget,
                        load_profile, perm_batch_cost, production_cost,
                        save_profile, solve_tiles)
from repro.tune.model import (SQUARE_SESSION_ARTIFACTS,
                              STANDALONE_SESSION_ARTIFACTS,
                              session_hoist_passes)
from repro.tune.solve import (BATCH_MAX, DEFAULT_BATCH, DEFAULT_BLOCK,
                              DEFAULT_CHUNK, DEFAULT_FEATURE_BLOCK)

KEY = jax.random.PRNGKey(7)


def _budget(working_bytes, backend="cpu"):
    return BackendBudget(backend=backend, working_bytes=working_bytes,
                         capacity_bytes=32 * 2**20, bandwidth=3e10,
                         latency=30e-6)


# --------------------------------------------------------------------------
# ledger/model parity — the two can never drift
# --------------------------------------------------------------------------
def test_model_reproduces_published_mantel_ratio():
    """The cost model's perm term IS the ledger's: the 10.97x headline
    (square_gather / condensed_fused at n=2048, B=32) falls out of
    ``perm_batch_cost`` untouched."""
    cost = perm_batch_cost(2048, 32, 65536, s=1)
    ledger = perm_traffic_floats(2048, 32)
    assert cost.traffic_floats == ledger["condensed_fused"]
    assert ledger["square_gather"] / cost.traffic_floats == \
        pytest.approx(10.97, abs=0.005)


def test_model_reproduces_published_api_session_passes():
    """The 11-vs-16 BENCH_api accounting from the model's session
    artifact lists + the ledger's pass table."""
    assert session_hoist_passes(SQUARE_SESSION_ARTIFACTS) == 11.0
    assert session_hoist_passes(STANDALONE_SESSION_ARTIFACTS) == 16.0
    # and the feature-backed column discounts, never inflates
    assert session_hoist_passes(SQUARE_SESSION_ARTIFACTS,
                                feature_backed=True) < 11.0


def test_model_production_parity_with_ledger():
    """``production_cost`` prices traffic with the ledger function
    itself, at every (n, d, block) point."""
    for n, d, b in [(100, 10, 32), (2048, 128, 256), (64, 8, 512)]:
        assert production_cost(n, d, b).traffic_floats == \
            production_floats(n, d, b)


def test_model_traffic_monotone_in_n_and_k():
    """Modeled traffic is monotone non-decreasing in n (per
    permutation) and in K (trivially linear: per-perm × K) — the
    sanity property that keeps the solver's argmin meaningful."""
    per_perm = [perm_batch_cost(n, 32, 65536).traffic_floats
                for n in (64, 128, 512, 2048, 4096)]
    assert all(a <= b for a, b in zip(per_perm, per_perm[1:]))
    prod = [production_cost(n, 64, 256).traffic_floats
            for n in (64, 128, 512, 2048)]
    assert all(a <= b for a, b in zip(prod, prod[1:]))
    for k1, k2 in [(99, 999), (999, 9999)]:
        assert per_perm[0] * k1 <= per_perm[0] * k2


# --------------------------------------------------------------------------
# solver properties
# --------------------------------------------------------------------------
def test_solver_choices_fit_stated_budget():
    """Property: across a spread of (n, d, budget), every solved tile's
    modeled tunable resident set fits the budget it was solved for."""
    rng = np.random.default_rng(0)
    for _ in range(25):
        n = int(rng.integers(8, 3000))
        d = int(rng.integers(2, 800))
        budget = _budget(int(rng.integers(256, 16 * 1024)) * 1024)
        t = solve_tiles(n, d, budget=budget)
        bf = budget.working_floats
        assert perm_batch_cost(n, t.batch_size, t.chunk,
                               s=2).resident_floats <= bf
        assert production_cost(n, d, t.block,
                               t.feature_block).resident_floats <= bf


def test_solver_never_models_worse_than_defaults():
    """The BENCH_tune gate at test scale: for every op the solved tiles
    model <= the effective traffic of the hand-picked constants, on
    loose and tight budgets alike."""
    for wb in (256 * 1024, 1 * 2**20, 16 * 2**20):
        for n, d in [(48, 8), (512, 64), (2048, 128), (300, None)]:
            t = solve_tiles(n, d, budget=_budget(wb))
            td = t.to_dict()
            for op in td["modeled"]:
                assert (td["modeled"][op]["traffic_floats"]
                        <= td["modeled_default"][op]["traffic_floats"]), \
                    (op, n, d, wb)


def test_solver_is_k_independent_and_capped():
    """batch/chunk are functions of (n, budget) only — no K parameter
    exists to leak into the engine's trace signature — and the batch
    caps at BATCH_MAX regardless of headroom."""
    import inspect
    assert "K" not in inspect.signature(solve_tiles).parameters
    assert "permutations" not in inspect.signature(solve_tiles).parameters
    t = solve_tiles(64, budget=_budget(64 * 2**20))
    assert t.batch_size <= BATCH_MAX


def test_solver_respects_int32_triangle_guard():
    """Satellite bugfix: the solver refuses n past the int32 triangle
    bound up front — auto-tuning can never hand the permutation kernels
    an n whose closed-form index would overflow."""
    with pytest.raises(ValueError, match="int32 triangle"):
        solve_tiles(MAX_TRIANGLE_N + 1)
    # the bound itself is fine
    t = solve_tiles(MAX_TRIANGLE_N, budget=_budget(2**20))
    assert t.batch_size >= 1


def test_solver_feature_block_and_block_shrink_only():
    """feature_block (per-chunk accumulator merges) and block (matvec
    row-panel partial sums) are value-affecting, so the solver may only
    ever SHRINK them from the defaults — with a roomy budget it returns
    the defaults exactly, which is what makes auto bitwise-identical to
    the default run whenever the default fits."""
    for wb in (64 * 1024, 2**20, 16 * 2**20):
        for n, d in [(128, 16), (2048, 512), (1000, 4)]:
            t = solve_tiles(n, d, budget=_budget(wb))
            assert t.feature_block <= min(DEFAULT_FEATURE_BLOCK, d)
            assert t.block <= DEFAULT_BLOCK
    roomy = solve_tiles(2048, 64, budget=_budget(64 * 2**20))
    assert roomy.block == DEFAULT_BLOCK
    assert roomy.feature_block == min(DEFAULT_FEATURE_BLOCK, 64)


def test_solved_defaults_match_constants():
    """The solver's one authoritative copy of each hand-picked constant
    is pinned against the modules that execute them."""
    from repro.kernels import permute_reduce_ops
    from repro.dist import driver
    assert DEFAULT_CHUNK == permute_reduce_ops.DEFAULT_CHUNK
    assert DEFAULT_BLOCK == driver._DEFAULT_BLOCK
    assert DEFAULT_FEATURE_BLOCK == driver._DEFAULT_FEATURE_BLOCK
    assert DEFAULT_BATCH == 32          # the Workspace battery default


# --------------------------------------------------------------------------
# budget: defaults, calibration, profile round-trip
# --------------------------------------------------------------------------
def test_detect_budget_backends():
    for be in ("cpu", "tpu", "gpu"):
        b = detect_budget(be, device_kind="TPU v5 lite")
        assert b.backend == be and b.working_bytes > 0
        assert b.working_bytes <= b.capacity_bytes
    assert detect_budget().backend == jax.default_backend()
    # no guessed defaults: an unknown TPU kind or backend is refused
    with pytest.raises(ValueError, match="TPU v4"):
        detect_budget("tpu", device_kind="TPU v4")
    with pytest.raises(ValueError, match="metal"):
        detect_budget("metal")


def test_calibration_profile_roundtrip(tmp_path):
    """calibrate() measures rate constants only (capacities stay
    static), and profiles survive the JSON round-trip."""
    base = detect_budget()
    cal = calibrate(base, small=1 << 10, large=1 << 16, reps=2)
    assert cal.source == "calibrated"
    assert cal.bandwidth > 0 and cal.latency >= 0
    assert cal.working_bytes == base.working_bytes
    path = str(tmp_path / "profile.json")
    save_profile(cal, path)
    loaded = load_profile(path)
    assert loaded.source == "profile"
    assert loaded.bandwidth == cal.bandwidth
    assert loaded.working_bytes == cal.working_bytes
    # and the solver accepts it
    t = solve_tiles(64, profile=path)
    assert t.budget.source == "profile"


# --------------------------------------------------------------------------
# ExecConfig auto plumbing
# --------------------------------------------------------------------------
def test_execconfig_accepts_and_validates_auto():
    ExecConfig(block="auto", feature_block="auto", batch_size="auto",
               chunk="auto")               # all fine
    assert ExecConfig(auto=True).needs_resolution
    assert ExecConfig(chunk="auto").needs_resolution
    assert not ExecConfig().needs_resolution
    for bad in ({"block": 0}, {"block": "big"}, {"chunk": -3},
                {"batch_size": "autotune"}, {"feature_block": 0}):
        with pytest.raises(ValueError):
            ExecConfig(**bad)
    # configs with auto knobs stay hashable (leaf-free pytree contract)
    hash(ExecConfig(auto=True))
    hash(ExecConfig(block="auto"))


def test_execconfig_resolve_materializes_all_knobs():
    cfg, tuned = ExecConfig(auto=True).resolve(256, 32)
    assert not cfg.needs_resolution and not cfg.auto
    for knob in ("block", "feature_block", "batch_size", "chunk"):
        assert isinstance(getattr(cfg, knob), int), knob
    assert tuned is not None and tuned.n == 256
    # no-op without auto semantics
    plain = ExecConfig()
    assert plain.resolve(256, 32) == (plain, None)


def test_execconfig_resolve_honors_explicit_knobs():
    """auto=True only solves knobs left at their defaults — explicitly
    pinned values pass through untouched."""
    cfg, tuned = ExecConfig(auto=True, block=64, chunk=2048).resolve(512, 16)
    assert cfg.block == 64 and cfg.chunk == 2048
    assert isinstance(cfg.batch_size, int)          # this one was solved
    assert tuned is not None


# --------------------------------------------------------------------------
# the acceptance battery: auto end-to-end, bitwise vs default
# --------------------------------------------------------------------------
def _feature_sessions(config):
    rng = np.random.default_rng(3)
    mk = lambda: rng.random((48, 12), dtype=np.float32) + 0.01  # noqa: E731
    return (Workspace.from_features(mk(), config=config),
            Workspace.from_features(mk(), config=config),
            Workspace.from_features(mk(), config=config))


def test_auto_battery_bitwise_identical_to_default():
    """ExecConfig(auto=True) end-to-end on a feature-backed session:
    every tile solver-chosen, every analysis result bitwise-identical
    per key to the default-config run."""
    ws_d, wy_d, wz_d = _feature_sessions(ExecConfig())
    ws_a, wy_a, wz_a = _feature_sessions(ExecConfig(auto=True))
    assert ws_a.tuned is not None
    g = np.arange(48) % 4

    ca = ws_a.pcoa(dimensions=6).coordinates
    cd = ws_d.pcoa(dimensions=6).coordinates
    assert (np.asarray(ca) == np.asarray(cd)).all()

    pairs = [
        (ws_a.permanova(g, permutations=99, key=KEY),
         ws_d.permanova(g, permutations=99, key=KEY)),
        (ws_a.anosim(g, permutations=99, key=KEY),
         ws_d.anosim(g, permutations=99, key=KEY)),
        (ws_a.permdisp(g, permutations=99, key=KEY, dimensions=6),
         ws_d.permdisp(g, permutations=99, key=KEY, dimensions=6)),
        (ws_a.mantel(wy_a, permutations=99, key=KEY),
         ws_d.mantel(wy_d, permutations=99, key=KEY)),
        (ws_a.partial_mantel(wy_a, wz_a, permutations=99, key=KEY),
         ws_d.partial_mantel(wy_d, wz_d, permutations=99, key=KEY)),
    ]
    for ra, rd in pairs:
        assert float(ra.statistic) == float(rd.statistic)
        assert float(ra.p_value) == float(rd.p_value)


def test_auto_one_program_serves_every_k():
    """Satellite bugfix gate: auto-tuning must not reintroduce the
    trailing-block recompile — the solved batch_size is K-independent,
    so different K values share ONE padded per_batch program and ONE
    kernels.permute_reduce program."""
    dm = random_distance_matrix(jax.random.PRNGKey(5), 40)
    dm2 = random_distance_matrix(jax.random.PRNGKey(6), 40)
    ws = Workspace(dm, config=ExecConfig(auto=True))
    with sentinel.expect("kernels.permute_reduce", max_programs=1):
        with sentinel.expect("stats.engine.per_batch", max_programs=1):
            ws.mantel(dm2, permutations=49, key=KEY)
            ws.mantel(dm2, permutations=17, key=KEY)
            ws.mantel(dm2, permutations=128, key=KEY)


def test_engine_batch_size_auto_resolves():
    """A config that never went through Workspace admission still
    resolves ``batch_size='auto'`` inside the engine, against the
    statistic's n."""
    x = random_distance_matrix(jax.random.PRNGKey(0), 36)
    y = random_distance_matrix(jax.random.PRNGKey(1), 36)
    stat = MantelStatistic(x.data, y.data, 36)
    r_auto = permutation_test(stat, permutations=45, key=KEY,
                              config=ExecConfig(batch_size="auto"))
    r_def = permutation_test(stat, permutations=45, key=KEY)
    assert float(r_auto.statistic) == float(r_def.statistic)
    assert float(r_auto.p_value) == float(r_def.p_value)


# --------------------------------------------------------------------------
# knob invariance (extends the engine batch-size invariance to the
# remaining tuned knobs)
# --------------------------------------------------------------------------
def test_results_invariant_to_block():
    """block tiles ROWS: each produced distance is computed from the
    full feature vector regardless of panel membership, so the
    condensed matrix is bitwise-invariant across tile sizes. The
    matvec-backed ordination re-associates panel partial sums, so
    coordinates are only fp-equal — which is why the solver treats
    block as freely tunable for production but the battery pins
    p-values, not coords, across blocks."""
    rng = np.random.default_rng(3)
    feats = rng.random((48, 12), dtype=np.float32) + 0.01
    base_dm = None
    for blk in (16, 48, 256, 1024):
        cond = np.asarray(Workspace.from_features(
            feats, config=ExecConfig(block=blk)).condensed())
        if base_dm is None:
            base_dm = cond
        else:
            assert (cond == base_dm).all(), blk

    dm = random_distance_matrix(jax.random.PRNGKey(2), 48)
    base_c = None
    for blk in (16, 48, 256):
        c = np.asarray(Workspace(
            dm, config=ExecConfig(block=blk)).pcoa(dimensions=5).coordinates)
        if base_c is None:
            base_c = c
        else:
            assert np.allclose(c, base_c, atol=1e-4), blk


def test_pvalues_invariant_to_chunk():
    """The observed statistic is chunk-free (the per_perm path never
    chunks) and, at a fixed key, the null count — hence the p-value —
    is stable across chunk choices."""
    x = random_distance_matrix(jax.random.PRNGKey(0), 36)
    y = random_distance_matrix(jax.random.PRNGKey(1), 36)
    rs = [permutation_test(
            MantelStatistic(x.data, y.data, 36, chunk=c),
            permutations=45, key=KEY, batch_size=8)
          for c in (None, 64, 256, 630)]
    for r in rs[1:]:
        assert float(r.statistic) == float(rs[0].statistic)
        assert float(r.p_value) == float(rs[0].p_value)


def test_feature_block_shrunk_results_close():
    """feature_block IS value-affecting (per-chunk merges) — a shrunk
    chunk must stay allclose and deliver the same p-values at test
    scale, which is why the solver only ever shrinks it."""
    rng = np.random.default_rng(9)
    feats = rng.random((40, 24), dtype=np.float32) + 0.01
    g = np.arange(40) % 4
    r1 = Workspace.from_features(
        feats, config=ExecConfig(feature_block=24)).permanova(
            g, permutations=49, key=KEY)
    r2 = Workspace.from_features(
        feats, config=ExecConfig(feature_block=8)).permanova(
            g, permutations=49, key=KEY)
    assert float(r1.statistic) == pytest.approx(float(r2.statistic),
                                                rel=1e-5)
    assert float(r1.p_value) == float(r2.p_value)


# --------------------------------------------------------------------------
# reporting
# --------------------------------------------------------------------------
def test_report_surfaces_resolved_tiles():
    """Satellite: report() shows the EXECUTED geometry — post-tune,
    post-snap — not just the requested knob values."""
    dm = random_distance_matrix(jax.random.PRNGKey(4), 30)
    ws = Workspace(dm, config=ExecConfig(auto=True))
    doc = ws.report().to_dict()
    tiles = doc["meta"]["tiles"]
    assert tiles["auto"] is True
    assert tiles["block_executed"] <= 30
    assert tiles["chunk_executed"] <= 30 * 29 // 2 + 7
    assert doc["meta"]["tune"]["n"] == 30
    assert doc["meta"]["tune"]["budget"]["backend"] == \
        jax.default_backend()
    # round-trips through JSON (CI uploads reports)
    json.dumps(doc)

    # a default session reports requested == executed-ish geometry and
    # no tune section
    ws2 = Workspace(dm)
    doc2 = ws2.report().to_dict()
    assert doc2["meta"]["tiles"]["auto"] is False
    assert "tune" not in doc2["meta"]
    assert ws2.config_requested is ws2.config


def test_workspace_refresh_resolves_for_new_n():
    """refresh(dm=...) with a different n re-solves from the REQUESTED
    config — the tuned tiles track the admitted data."""
    dm1 = random_distance_matrix(jax.random.PRNGKey(1), 24)
    dm2 = random_distance_matrix(jax.random.PRNGKey(2), 120)
    ws = Workspace(dm1, config=ExecConfig(auto=True))
    t1 = dataclasses.replace(ws.tuned)
    ws.refresh(dm=dm2)
    assert ws.tuned.n == 120 and t1.n == 24
    assert ws.config_requested.auto      # the intent survives
    assert not ws.config.auto            # the resolution is concrete
