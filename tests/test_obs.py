"""repro.obs tests: span tracer semantics and exports, the analytic
traffic registry's parity with the published BENCH ratios (10.97x mantel,
11-vs-16 api passes), the recompile sentinel's one-program-per-shape
guarantee across K values, RunReport assembly from an instrumented
Workspace battery, and the disabled path's zero-overhead contract."""

import json
import time

import jax
import numpy as np
import pytest

from repro.api import ExecConfig, Workspace
from repro.core import random_distance_matrix
from repro.obs import (FEATURE_HOIST_PASSES, HOIST_PASSES, NULL_OBS,
                       CompileSentinel, Ledger, ObsConfig, ProfilerSpan,
                       RecompileError, RunReport, Span, Tracer, build_report,
                       current_obs, perm_traffic_floats, production_floats,
                       sentinel)

KEY = jax.random.PRNGKey(7)


def _features(seed, n=40, d=8):
    rng = np.random.default_rng(seed)
    return (rng.random((n, d)) + 0.01).astype(np.float32)


def _obs_ws(seed, n=40, d=8, **cfg):
    config = ExecConfig(obs=ObsConfig(enabled=True), **cfg)
    return Workspace.from_features(_features(seed, n, d), config=config)


# --------------------------------------------------------------------------
# registry parity: the ledger reproduces the published BENCH accounting
# --------------------------------------------------------------------------
def test_registry_parity_mantel_headline():
    """The 10.97x BENCH_mantel headline is square_gather/condensed_fused
    at n=2048, B=32 — pinned against the ONE consolidated registry."""
    floats = perm_traffic_floats(2048, 32)
    ratio = floats["square_gather"] / floats["condensed_fused"]
    assert ratio == pytest.approx(10.97, abs=0.005)
    # and the eager-original model stays the most expensive formulation
    assert floats["original"] > floats["square_gather"]


def test_registry_parity_api_session_passes():
    """The BENCH_api 4-analysis battery: 11 n²-passes for one shared
    Workspace vs 16 for per-call standalone sessions, straight from the
    registry's pass table."""
    shared = sum(HOIST_PASSES[a] for a in
                 ("operator", "gram", "condensed", "ranks", "coords"))
    assert shared == 11.0
    standalone = (
        (HOIST_PASSES["operator"] + HOIST_PASSES["coords"])    # pcoa
        + HOIST_PASSES["gram"]                                 # permanova
        + (HOIST_PASSES["operator"] + HOIST_PASSES["coords"])  # permdisp
        + (HOIST_PASSES["condensed"] + HOIST_PASSES["ranks"])  # anosim
    )
    assert standalone == 16.0


def test_registry_parity_benchmarks_import_the_registry():
    """Satellite: the benchmark scripts no longer own private copies of
    the audited tables — they ARE the registry objects."""
    from benchmarks import bench_api, bench_dist, bench_mantel
    assert bench_api._PASSES is HOIST_PASSES
    assert bench_dist._PASSES_BASE is HOIST_PASSES
    assert bench_dist._PASSES_FUSED is FEATURE_HOIST_PASSES
    assert bench_mantel.perm_traffic_floats is perm_traffic_floats


def test_feature_table_discounts():
    """The feature-backed column only differs where the square-free
    production makes builds cheaper — never more expensive."""
    assert set(FEATURE_HOIST_PASSES) == set(HOIST_PASSES)
    for k in HOIST_PASSES:
        assert FEATURE_HOIST_PASSES[k] <= HOIST_PASSES[k], k
    assert FEATURE_HOIST_PASSES["operator"] == 0.0   # fused accumulators
    assert FEATURE_HOIST_PASSES["coords"] == 2.0     # condensed matvecs


def test_production_floats_formula():
    # ceil(n/b) panels stream the full (n, d) table + one read of x
    assert production_floats(256, 32, 64) == 4 * 256 * 32 + 256 * 32
    assert production_floats(100, 10, 256) == 100 * 10 + 100 * 10  # b -> n


# --------------------------------------------------------------------------
# ledger
# --------------------------------------------------------------------------
def test_ledger_charges_and_totals():
    led = Ledger()
    led.charge_hoist("gram", 100)
    led.charge_hoist("coords", 100, table=FEATURE_HOIST_PASSES)
    led.charge_perm_batch("mantel", 100, permutations=64, batch=32)
    led.charge_production(100, 8, 50)
    assert led.hoist_passes() == 4.0 + 2.0
    per = perm_traffic_floats(100, 32)["condensed_fused"]
    expect = (4.0 * 100 * 100 + 2.0 * 100 * 100 + per * 64
              + production_floats(100, 8, 50))
    assert led.total_floats() == pytest.approx(expect)
    assert led.total_bytes() == pytest.approx(4.0 * expect)
    by_op = led.by_op()
    assert set(by_op) == {"hoist:gram", "hoist:coords", "perm:mantel",
                          "production"}
    assert by_op["perm:mantel"]["count"] == 1
    # every entry keeps the parameter point for offline re-audit
    entry = led.entries[2]
    assert entry.params["batch"] == 32
    assert entry.params["model"] == "condensed_fused"
    assert entry.bytes == 4.0 * entry.floats


# --------------------------------------------------------------------------
# span tracer
# --------------------------------------------------------------------------
def test_tracer_nesting_and_phase_accounting():
    t = Tracer()
    with t.span("outer", phase="hoist", n=10):
        with t.span("inner", phase="solve"):
            pass
        t.record("pre_timed", 0.5, phase="step")
    (root,) = t.spans
    assert root.name == "outer" and root.phase == "hoist"
    assert [c.name for c in root.children] == ["inner", "pre_timed"]
    assert root.duration >= root.children[0].duration
    assert t.count() == 3 and t.count("solve") == 1
    assert t.total("step") == pytest.approx(0.5)


def test_tracer_rejects_unknown_phase():
    with pytest.raises(ValueError, match="phase"):
        Tracer().span("x", phase="warp")


def test_span_end_before_begin_is_an_error():
    t = Tracer()
    with pytest.raises(RuntimeError, match="before begin"):
        t.span("x").end()


def test_tracer_exports_json_and_chrome_trace():
    t = Tracer()
    with t.span("a", phase="hoist", impl="xla"):
        with t.span("b", phase="per_perm"):
            pass
    tree = json.loads(t.to_json())
    assert tree[0]["name"] == "a"
    assert tree[0]["children"][0]["name"] == "b"
    events = t.to_chrome_trace()
    assert {e["name"] for e in events} == {"a", "b"}
    for e in events:
        assert e["ph"] == "X" and e["dur"] >= 0.0
    a = next(e for e in events if e["name"] == "a")
    assert a["cat"] == "hoist" and a["args"]["impl"] == "xla"
    # tree_lines renders one line per span, child indented under parent
    lines = t.tree_lines()
    assert len(lines) == 2 and "a [hoist]" in lines[0]


def test_ambient_session_stack():
    class FakeSession:
        enabled = True

    s = FakeSession()
    t = Tracer()
    assert current_obs() is NULL_OBS
    with t.span("outer", session=s):
        assert current_obs() is s
    assert current_obs() is NULL_OBS


# --------------------------------------------------------------------------
# the disabled path: zero-overhead contract
# --------------------------------------------------------------------------
def test_null_singletons_are_process_wide():
    """The session-less path keeps no state: a disabled session IS the
    shared singleton, and its span is the profiler annotation alone —
    no tracer state, no ledger entry, no ambient session."""
    span = NULL_OBS.span("anything", phase="hoist", n=10)
    assert isinstance(span, ProfilerSpan) and not isinstance(span, Span)
    with span:
        assert current_obs() is NULL_OBS
    assert span.add(x=1) is span and span.begin().end() is span
    assert NULL_OBS.charge_hoist("gram", 100) is None
    assert not NULL_OBS.enabled
    # a default Workspace rides the singleton — no session object exists
    ws = Workspace(random_distance_matrix(KEY, 12))
    assert ws.obs is NULL_OBS
    assert ws.cache.obs is NULL_OBS


def test_disabled_span_fast_path_overhead():
    """The satellite's <2% overhead claim, asserted where it is testable
    deterministically: the per-call cost of the disabled span path is
    sub-microsecond-scale (generous 20µs/call bound vs the engine's
    multi-ms analysis calls it brackets)."""
    calls = 20_000
    t0 = time.perf_counter()
    for _ in range(calls):
        with current_obs().span("engine.x", phase="per_perm", n=40,
                                permutations=999, batch_size=32):
            pass
    per_call = (time.perf_counter() - t0) / calls
    assert per_call < 20e-6


# --------------------------------------------------------------------------
# recompile sentinel
# --------------------------------------------------------------------------
def test_sentinel_counts_traces_and_programs():
    s = CompileSentinel()
    s.note("f", (10, 32))
    s.note("f", (10, 32))
    s.note("f", (20, 32))
    s.note("g")                       # signature-less: trace count only
    assert s.traces("f") == 3 and s.programs("f") == 2
    assert s.traces("g") == 1 and s.programs("g") == 0
    snap = s.snapshot()
    s.note("f", (30, 32))
    assert s.since(snap) == {"f": {"traces": 1, "programs": 1}}
    assert s.since(s.snapshot()) == {}


def test_sentinel_expect_raises_on_budget_breach():
    s = CompileSentinel()
    with s.expect("f", max_programs=1):
        s.note("f", (1,))
    with pytest.raises(RecompileError, match="distinct programs"):
        with s.expect("f", max_programs=1):
            s.note("f", (2,))
            s.note("f", (3,))
    with pytest.raises(RecompileError, match="traces"):
        with s.expect("g", max_programs=9, max_traces=1):
            s.note("g")
            s.note("g")


def test_one_permute_reduce_program_serves_any_k():
    """THE acceptance invariant, now runtime-assertable: across two
    different permutation counts (padded per_batch path), the batched
    condensed kernel compiles exactly ONE program — jax caches the inner
    jit's trace by abstract values even across outer engine retraces.

    n=41 is unique to this test: the process-wide jit cache must be cold
    for this shape or no trace lands inside the sentinel window."""
    ws, wsy = _obs_ws(0, n=41), _obs_ws(1, n=41)
    base = sentinel.snapshot()
    with sentinel.expect("kernels.permute_reduce", max_programs=1):
        ws.mantel(wsy, permutations=49, key=KEY)   # 2 padded tiles of 32
        ws.mantel(wsy, permutations=17, key=KEY)   # 1 padded tile
    delta = sentinel.since(base)["kernels.permute_reduce"]
    assert delta == {"traces": 1, "programs": 1}
    # the engine-level counter sees both outer retraces (K is static on
    # the outer jit) but still exactly one per_batch program
    eng = sentinel.since(base)["stats.engine.per_batch"]
    assert eng["traces"] == 2 and eng["programs"] == 1


# --------------------------------------------------------------------------
# RunReport: the instrumented battery end-to-end
# --------------------------------------------------------------------------
def test_feature_backed_battery_report():
    """Acceptance: the full 6-analysis battery on an obs-enabled feature-
    backed Workspace yields a RunReport whose ledger carries every hoist,
    permutation batch and the production sweep, whose hoist passes match
    the feature-backed registry column, and whose compile window holds
    the one-program guarantee."""
    ws, wsy, wsz = _obs_ws(2), _obs_ws(3), _obs_ws(4)
    g = np.arange(40) % 4
    ws.pcoa(dimensions=5)
    ws.permanova(g, permutations=49, key=KEY)
    ws.permdisp(g, permutations=49, key=KEY, dimensions=5)
    ws.anosim(g, permutations=49, key=KEY)
    ws.mantel(wsy, permutations=49, key=KEY)
    ws.partial_mantel(wsy, wsz, permutations=49, key=KEY)

    rep = ws.report(meta={"suite": "test"})
    assert isinstance(rep, RunReport)
    assert rep.meta["backing"] == "features" and rep.meta["suite"] == "test"

    # ledger: every instrumented op charged, none double-charged
    by_op = rep.ledger["by_op"]
    for op in ("production", "hoist:condensed", "hoist:operator",
               "hoist:coords", "hoist:ranks", "hoist:moments",
               "perm:mantel", "perm:partial_mantel", "perm:anosim"):
        assert op in by_op, op
        assert by_op[op]["count"] == 1, op
    # feature-backed column: condensed .5 + operator 0 + dist_means 0 +
    # coords 2 + ranks 1 + moments .5 = 4 n²-passes for the full battery
    assert rep.hoist_passes == pytest.approx(4.0)
    assert rep.total_bytes == pytest.approx(4.0 * rep.ledger["total_floats"])
    per = perm_traffic_floats(40, 32)["condensed_fused"]
    # 49 permutations pad to 2 tiles of 32 -> 64 charged draws
    assert by_op["perm:mantel"]["floats"] == pytest.approx(per * 64)

    # spans: the ws.* roots with their hoists nested beneath
    roots = [s["name"] for s in rep.spans]
    for name in ("ws.pcoa", "ws.permanova", "ws.permdisp", "ws.anosim",
                 "ws.mantel", "ws.partial_mantel"):
        assert name in roots, name
    pcoa_span = rep.spans[roots.index("ws.pcoa")]
    nested = [c["name"] for c in pcoa_span.get("children", ())]
    assert "hoist:coords" in nested

    # cache + compile sections are live
    assert rep.cache["misses"]
    assert rep.programs("kernels.permute_reduce") >= 1

    # the document round-trips
    doc = json.loads(rep.to_json())
    assert doc["meta"]["n"] == 40
    assert doc["ledger"]["hoist_passes"] == pytest.approx(4.0)


def test_square_backed_battery_reproduces_bench_api_11_passes():
    """Acceptance: the square-backed BENCH_api battery (pcoa + permanova
    + permdisp + anosim) charges exactly the 11 n²-passes the published
    accounting reports — live, from the instrumented HoistCache."""
    dm = random_distance_matrix(KEY, 36)
    ws = Workspace(dm, config=ExecConfig(obs=ObsConfig(enabled=True)))
    g = np.arange(36) % 3
    ws.pcoa(dimensions=5)
    ws.permanova(g, permutations=49, key=KEY)
    ws.permdisp(g, permutations=49, key=KEY, dimensions=5)
    ws.anosim(g, permutations=49, key=KEY)
    rep = ws.report()
    assert rep.meta["backing"] == "distance_matrix"
    assert rep.hoist_passes == pytest.approx(11.0)
    assert rep.ledger["by_op"]["hoist:gram"]["floats"] == 4.0 * 36 * 36


def test_disabled_report_still_carries_cache_and_sentinel():
    ws = Workspace(random_distance_matrix(KEY, 12))   # obs off (default)
    ws.pcoa(dimensions=3)
    rep = ws.report()
    assert rep.spans == [] and rep.ledger == {}
    assert rep.meta["obs_enabled"] is False
    assert any("coords" in k for k in rep.cache["misses"])
    assert rep.compile == sentinel.snapshot()         # full process view


def test_report_save_roundtrip(tmp_path):
    ws = _obs_ws(5, n=16, d=4)
    ws.pcoa(dimensions=3)
    path = str(tmp_path / "report.json")
    ws.report().save(path)
    with open(path) as f:
        doc = json.load(f)
    assert doc["meta"]["n"] == 16 and doc["spans"]


def test_spans_accumulate_across_refresh_generations():
    ws = _obs_ws(6, n=16, d=4)
    ws.pcoa(dimensions=3)
    ws.refresh()
    ws.pcoa(dimensions=3)
    rep = ws.report()
    assert rep.meta["generation"] == 1
    # both generations' builds were charged to the session ledger
    assert rep.ledger["by_op"]["hoist:coords"]["count"] == 2
    # ...but the cache section reflects only the live generation
    assert sum(rep.cache["misses"].values()) < len(rep.ledger["entries"])


# --------------------------------------------------------------------------
# config plumbing
# --------------------------------------------------------------------------
def test_obs_config_validation_and_execconfig_integration():
    with pytest.raises(ValueError):
        ObsConfig(enabled="yes")
    with pytest.raises(ValueError, match="obs"):
        ExecConfig(obs="on")
    # None coerces to the disabled default; configs stay hashable pytree
    # metadata (the jit-cache key contract)
    assert ExecConfig(obs=None) == ExecConfig()
    assert hash(ExecConfig(obs=ObsConfig())) == hash(ExecConfig())
    assert ExecConfig(obs=ObsConfig(enabled=True)) != ExecConfig()
    assert not ExecConfig().obs.enabled


def test_build_report_without_session():
    rep = build_report(None, cache=None, meta={"x": 1})
    assert rep.meta["x"] == 1 and rep.cache == {}
    assert rep.spans == [] and rep.ledger == {}
