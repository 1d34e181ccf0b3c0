"""The program's own instruments on the profiler's clock: the named
scopes of the null-distribution program and the compiled HLO the
sentinel keeps for them (read into a scope map by the benchmark's
``scopes``), the program-preparation counter, the profiler
annotation every span opens with or without a session, and the serve
scheduler's per-tile spans."""

import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.api import ExecConfig, Workspace
from repro.obs import ObsConfig, Tracer
from benchmarks.chip.scopes import hlo_scopes, merged_scopes
from repro.obs.compile import CompileSentinel, sentinel
from repro.runtime.monitor import StepMonitor
from repro.serve import AnalysisService, ServeConfig
from repro.stats import engine

KEY = jax.random.PRNGKey(3)
MODULE = "jit__null_distribution"


def _pair(n, d=6, seed=0):
    rng = np.random.default_rng(seed)
    return [Workspace.from_features(rng.random((n, d)).astype(np.float32))
            for _ in range(2)]


def _mantel_call(n):
    """The Mantel statistic of a fresh pair and the engine call's static
    arguments, as ``Workspace.mantel`` builds them."""
    x, y = _pair(n)
    stat, _ = x.statistic("mantel", other=y)
    return x, y, stat


def _compiled_text(stat, permutations=40, batch_size=8):
    return engine._null_distribution.lower(
        stat, KEY, permutations=permutations,
        batch_size=batch_size).compile().as_text()


def _paths(texts):
    """``{instruction: scope path}`` of a module's compiled texts."""
    return {n: path for n, (path, _) in merged_scopes(texts).items()}


def _instructions(text, opcodes):
    """Instruction names of the given opcodes, computation-level ones
    only (no parameters of a computation header)."""
    rx = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = \S+ (" +
                    "|".join(opcodes) + r")\(")
    return [m[1] for m in map(rx.match, text.splitlines()) if m]


# --------------------------------------------------------------------------
# device scopes
# --------------------------------------------------------------------------
def test_null_distribution_hlo_carries_the_definition_scopes():
    _, _, stat = _mantel_call(29)
    text = _compiled_text(stat)
    ops = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("perm.orders", "perm.hoist", "perm.draws"):
        assert any(f"/{scope}/" in op for op in ops), scope
    # inside the draws, the kernel's own steps
    for inner in ("index", "gather", "reduce"):
        assert any(re.search(rf"/perm\.draws/.*/{inner}/", op)
                   for op in ops), inner


def test_row_layout_draws_carry_the_definition_scopes():
    """The row layout keeps the scopes the metrics bind to: its row
    gathers under ``perm.draws``/``gather``, its multiply-reduce under
    ``reduce``, the inverse orders under ``index``."""
    import dataclasses
    _, _, stat = _mantel_call(29)
    ops = set(re.findall(r'op_name="([^"]*)"', _compiled_text(
        dataclasses.replace(stat, layout="rows"))))
    for scope in ("perm.orders", "perm.hoist", "perm.draws"):
        assert any(f"/{scope}/" in op for op in ops), scope
    for inner in ("index", "gather", "reduce"):
        assert any(re.search(
            rf"/perm\.draws/.*permute_reduce_rows.*/{inner}/", op)
            for op in ops), inner


def test_tree_hoist_unifrac_and_pcoa_carry_their_scopes():
    """The tree hoist's program under ``dist.tree_hoist`` (a span of the
    same name in the session, one sentinel program per (n, T, B)), the
    UniFrac metric's accumulate under ``dist.unifrac`` in production,
    and the fsvd solve under ``pcoa.solve``: the scopes ``tree_hoist_ms``,
    ``unifrac_production_ms`` and ``pcoa_ms`` read."""
    from repro.dist import PhyloTree
    rng = np.random.default_rng(12)
    tree = PhyloTree.from_newick("(((A:1,B:2):1,C:1):2,(D:1,E:3):1);")
    before = sentinel.snapshot()
    ws = Workspace.from_features(
        rng.poisson(1.0, (14, 5)).astype(np.float32),
        metric="unweighted_unifrac", tree=tree,
        config=ExecConfig(obs=ObsConfig(enabled=True)))
    ws.pcoa(dimensions=3)

    def walk(spans):
        for sp in spans:
            yield sp
            yield from walk(sp.children)

    (prod,) = [sp for sp in walk(ws.obs.tracer.spans)
               if sp.name == "ws.produce_distances"]
    (hoist,) = [c for c in prod.children if c.name == "dist.tree_hoist"]
    assert hoist.attrs == {"n": 14, "d": 5, "branches": 8}
    assert sentinel.since(before)["dist.tree_hoist"]["programs"] == 1
    for module, scope in (("jit__tree_hoist", "dist.tree_hoist"),
                          ("jit__panel_stats", "dist.unifrac"),
                          ("jit__randomized_eigh_matfree", "pcoa.solve")):
        paths = _paths(sentinel.hlo_texts(module)).values()
        assert any(scope in p.split("/") for p in paths), (module, scope)
    # UniFrac production's shared-length product: every dot of its
    # program lies under the metric's scope
    texts = [t for t in sentinel.hlo_texts("jit__panel_stats")
             if "dist.unifrac" in t]
    dots = [(t, name) for t in texts
            for name in _instructions(t, ("dot", "convolution"))]
    assert dots
    for t, name in dots:
        assert "dist.unifrac" in hlo_scopes(t)[name][0].split("/"), name


@pytest.mark.parametrize("metric, reduce", [
    ("unweighted_unifrac", "product"), ("braycurtis", "elementwise")])
def test_production_span_and_tiles_name_the_reduce(metric, reduce):
    """The production span says which panel body ran; a product metric
    reports the whole width it reads as its executed feature block."""
    from repro.dist import PhyloTree
    rng = np.random.default_rng(14)
    tree = PhyloTree.from_newick("(((A:1,B:2):1,C:1):2,(D:1,E:3):1);")
    ws = Workspace.from_features(
        rng.poisson(1.0, (11, 5)).astype(np.float32), metric=metric,
        tree=tree if metric == "unweighted_unifrac" else None,
        config=ExecConfig(feature_block=3, obs=ObsConfig(enabled=True)))
    ws.condensed()

    def walk(spans):
        for sp in spans:
            yield sp
            yield from walk(sp.children)

    (prod,) = [sp for sp in walk(ws.obs.tracer.spans)
               if sp.name == "dist.pairwise_condensed"]
    assert prod.attrs["reduce"] == reduce
    width = tree.num_branches if reduce == "product" else 3
    assert ws.resolved_tiles()["feature_block_executed"] == width


def test_runs_are_counted_by_the_signature_of_each_compiled_program():
    """Production's panels, the tree hoist and the fsvd solve count their
    executions under the signatures of the programs ``compiled`` hands
    over; a second ``compiled`` call compiles nothing again."""
    from repro.dist import PhyloTree
    rng = np.random.default_rng(5)
    tree = PhyloTree.from_newick("(((A:1,B:2):1,C:1):2,(D:1,E:3):1);")
    table = rng.poisson(1.0, (21, 5)).astype(np.float32)
    modules = ("jit__tree_hoist", "jit__panel_stats",
               "jit__randomized_eigh_matfree")
    before = {m: sentinel.runs(m) for m in modules}
    ws = Workspace.from_features(table, metric="unweighted_unifrac",
                                 tree=tree, config=ExecConfig(block=8))
    ws.pcoa(dimensions=3, key=4)
    moved = {m: {sig: n - before[m].get(sig, 0)
                 for sig, n in sentinel.runs(m).items()
                 if n > before[m].get(sig, 0)} for m in modules}
    assert list(moved["jit__tree_hoist"].values()) == [1]
    assert list(moved["jit__panel_stats"].values()) == [3]  # 21 rows by 8
    assert list(moved["jit__randomized_eigh_matfree"].values()) == [1]
    for m in modules:
        assert set(moved[m]) <= set(sentinel.compiled(m)), m
    prep = sentinel.prep()
    texts = sentinel.compiled("jit__panel_stats")
    again = sentinel.compiled("jit__panel_stats")
    assert again == texts and sentinel.prep_since(prep) == {}
    assert sentinel.hlo_texts("jit__panel_stats") == list(texts.values())


def test_compiled_compiles_afresh_only_a_text_without_the_scope():
    """Where the run's arguments were not committed, ``compiled`` hands
    back the executable that ran, with no compile. A text that lacks the
    scope asked for (as one loaded from a cache entry that other code
    wrote would) is compiled once more, apart from jax's executables,
    and then kept."""
    from repro.dist import PhyloTree
    rng = np.random.default_rng(8)
    tree = PhyloTree.from_newick("((A:1,(B:2,C:1):1):2,(D:1,E:3):1);")
    Workspace.from_features(rng.poisson(1.0, (19, 5)).astype(np.float32),
                            metric="unweighted_unifrac",
                            tree=tree).condensed()
    compiles = []

    def listen(event, seconds, **kwargs):
        if event.endswith("backend_compile_duration"):
            compiles.append(event)

    sentinel._texts.pop("jit__tree_hoist", None)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        ran = sentinel.compiled("jit__tree_hoist", "dist.tree_hoist")
        assert compiles == [] and ((19, 5), 8) in ran
        assert all("dist.tree_hoist" in t for t in ran.values())
        fresh = sentinel.compiled("jit__tree_hoist", "pcoa.solve")
        assert len(compiles) == len(ran)
        assert sentinel.compiled("jit__tree_hoist", "pcoa.solve") == fresh
        assert len(compiles) == len(ran)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    for signature, text in ran.items():
        assert _paths([fresh[signature]]) == _paths([text])


def test_scope_map_names_every_fusion_sort_and_gather():
    _, _, stat = _mantel_call(31)
    static = {"permutations": 40, "batch_size": 8}
    # a sentinel of its own, so no other program of the module is merged
    s = CompileSentinel()
    s.note("stats.engine.null_distribution", (31,),
           engine._null_distribution, (stat, KEY), static)
    scope_of = _paths(s.hlo_texts(MODULE + "(1234)"))
    text = _compiled_text(stat, **static)
    wanted = _instructions(text, ("fusion", "sort", "gather"))
    assert wanted
    for name in wanted:
        assert name in scope_of, name
        path = scope_of[name].split("/")
        assert {"perm.orders", "perm.hoist", "perm.draws"} & set(path), \
            (name, scope_of[name])
    # the gather scope sits inside the draws; the gather primitives of
    # the hoist's searchsorted and of the order gathers are not in it
    gathers = [n for n, p in scope_of.items() if "gather" in p.split("/")]
    assert gathers
    assert all("perm.draws" in scope_of[n].split("/") for n in gathers)
    indexed = [n for n, p in scope_of.items() if "index" in p.split("/")]
    assert indexed and not set(indexed) & set(gathers)
    assert s.hlo_texts("jit_never_traced") == []
    # the texts are compiled with the metadata in the cache key, and the
    # flag is given back
    assert not jax.config.jax_compilation_cache_include_metadata_in_key


def test_engine_calls_record_their_program_for_the_map():
    x, y = _pair(33, seed=2)
    x.mantel(y, permutations=40, key=KEY)
    scope_of = _paths(sentinel.hlo_texts(MODULE))
    assert any("perm.draws" in p.split("/") for p in scope_of.values())


def test_scope_map_leaves_out_names_two_programs_disagree_on():
    s = CompileSentinel()
    for n in (21, 22):
        _, _, stat = _mantel_call(n)
        s.note("stats.engine.null_distribution", (n,),
               engine._null_distribution, (stat, KEY),
               {"permutations": 16, "batch_size": 8})
    one = CompileSentinel()
    one.note("stats.engine.null_distribution", (22,),
             engine._null_distribution, (stat, KEY),
             {"permutations": 16, "batch_size": 8})
    merged = _paths(s.hlo_texts(MODULE))
    alone = _paths(one.hlo_texts(MODULE))
    assert set(merged) <= set(alone)
    assert all(alone[k] == v for k, v in merged.items())


def test_hlo_scopes_inherit_where_an_instruction_has_no_op_name():
    text = "\n".join([
        "HloModule jit_f, entry_computation_layout={()->f32[]}",
        "%fused (p: f32[]) -> f32[] {",
        '  ROOT %a = f32[] add(%p, %p), metadata={op_name="jit(f)/s/add"}',
        "}",
        "%body (t: f32[]) -> f32[] {",
        "  %c = f32[] copy(%t)",
        "  ROOT %fusion.2 = f32[] fusion(%c), kind=kLoop, calls=%fused",
        "}",
        "ENTRY %main (x: f32[]) -> f32[] {",
        '  %while.1 = f32[] while(%x), condition=%body, body=%body, '
        'metadata={op_name="jit(f)/loop/while"}',
        "  ROOT %copy.3 = f32[] copy(%while.1)",
        "}",
    ])
    # the primitive ("add", "while") is not part of the scope path
    assert hlo_scopes(text) == {"a": ("jit(f)/s", "op_name"),
                                "c": ("jit(f)/s", "users"),
                                "fusion.2": ("jit(f)/s", "callees"),
                                "while.1": ("jit(f)/loop", "op_name"),
                                "copy.3": ("", "caller")}  # the entry's


# --------------------------------------------------------------------------
# the program-preparation counter
# --------------------------------------------------------------------------
def test_prep_counter_grows_on_a_new_signature_only():
    x, y = _pair(37, seed=4)
    base = sentinel.prep()
    t0 = sentinel.prep_seconds()
    x.mantel(y, permutations=24, key=KEY)          # n=37: a new program
    first = sentinel.prep_since(base)
    assert first["seconds"] > 0
    assert sentinel.prep_seconds() == pytest.approx(t0 + first["seconds"])
    for kind in ("trace", "lower", "compile"):
        assert first[kind]["count"] >= 1 and first[kind]["seconds"] > 0
    entry = first["by_entry"]["stats.engine.null_distribution"]
    assert entry["trace"]["count"] == 1 and entry["compile"]["count"] == 1
    # summed trace seconds count nested traces again; the wall total not
    assert first["seconds"] <= sum(first[k]["seconds"]
                                   for k in ("trace", "lower", "compile"))

    again = sentinel.prep()
    x.mantel(y, permutations=24, key=KEY)          # a cached call
    moved = sentinel.prep_since(again)
    assert "stats.engine.null_distribution" not in moved.get("by_entry", {})

    before, prep = sentinel.snapshot(), sentinel.prep()
    assert sentinel.hlo_texts(MODULE)              # lowers and compiles
    assert sentinel.prep_since(prep) == {}
    assert sentinel.since(before) == {}            # and notes no trace


def test_prep_counter_merges_nested_intervals():
    s = CompileSentinel()
    trace = "/jax/core/compile/jaxpr_trace_duration"
    s._on_span(trace, 2.0, 3.0, fun_name="inner")
    s._on_span(trace, 1.0, 4.0, fun_name="outer")   # encloses inner
    s._on_span(trace, 6.0, 7.0, fun_name="later")
    s._on_span(trace, 4.5, 5.0, fun_name="other_thread")   # ends earlier
    s._on_span(trace, 3.5, 4.6, fun_name="bridge")         # joins two
    s._on_span("/jax/some/other_event", 0.0, 100.0)
    assert s._wall == [[1.0, 5.0], [6.0, 7.0]]
    assert s.prep_seconds() == pytest.approx(5.0)
    s._on_duration(trace, 0.5, fun_name="f")
    s._on_duration("/jax/core/compile/backend_compile_duration", 0.25,
                   fun_name="jit(f)")
    s._on_event("/jax/compilation_cache/cache_hits")
    prep = s.prep()
    assert prep["trace"] == {"count": 1, "seconds": 0.5}
    assert prep["cache_hits"] == 1
    assert prep["by_entry"] == {"jit_f": {
        "trace": {"count": 1, "seconds": 0.5},
        "compile": {"count": 1, "seconds": 0.25}}}


# --------------------------------------------------------------------------
# spans reach the profiler without a session
# --------------------------------------------------------------------------
def _host_event_names(logdir):
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    assert files
    names = set()
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    return names


def test_profiler_capture_holds_spans_without_a_session(tmp_path):
    x, y = _pair(23, seed=6)
    assert not x.obs.enabled
    x.mantel(y, permutations=16, key=KEY)          # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        x.mantel(y, permutations=16, key=KEY)
    finally:
        jax.profiler.stop_trace()
    names = _host_event_names(str(tmp_path))
    for span in ("ws.mantel", "engine.mantel", "engine.finish"):
        assert span in names, span


def test_session_spans_with_the_tracer_off_still_annotate(tmp_path):
    cfg = ExecConfig(obs=ObsConfig(enabled=True, spans=False))
    rng = np.random.default_rng(8)
    jax.profiler.start_trace(str(tmp_path))
    try:
        ws = Workspace.from_features(
            rng.random((12, 4)).astype(np.float32), config=cfg)
    finally:
        jax.profiler.stop_trace()
    assert ws.obs.tracer.spans == []
    names = _host_event_names(str(tmp_path))
    assert {"ws.from_features", "ws.upload", "ws.validate"} <= names


def test_from_features_spans_nest_in_a_session():
    rng = np.random.default_rng(9)
    ws = Workspace.from_features(rng.random((12, 4)).astype(np.float32),
                                 config=ExecConfig(
                                     obs=ObsConfig(enabled=True)))
    (root,) = [s for s in ws.obs.tracer.spans
               if s.name == "ws.from_features"]
    assert [c.name for c in root.children] == ["ws.upload", "ws.validate"]
    assert root.attrs == {"n": 12, "d": 4}


# --------------------------------------------------------------------------
# the scheduler's per-tile spans
# --------------------------------------------------------------------------
def _served(tmp_path, seed, tracer=None):
    """A service that has run one 40-draw Mantel request in tiles of 16;
    its monitor gets ``tracer`` where one is given."""
    svc = AnalysisService(ServeConfig(
        timeout_s=None, auto_tune=False, batch_size=16,
        journal_path=str(tmp_path / "serve.journal")))
    if tracer is not None:
        svc.scheduler.monitor = StepMonitor(
            deadline_factor=svc.config.deadline_factor, tracer=tracer)
    rng = np.random.default_rng(seed)
    for sid in ("x", "y"):
        svc.upload(sid, features=rng.random((24, 5)).astype(np.float32))
    h = svc.submit("x", "mantel", other="y", permutations=40, key=1)
    svc.run()
    assert h.result is not None
    return svc


def test_scheduler_spans_nest_under_the_step(tmp_path):
    tracer = Tracer()
    svc = _served(tmp_path, 10, tracer)
    steps = [s for s in tracer.spans if s.name == "step"]
    assert len(steps) == svc.scheduler.tiles_run == 3
    for step in steps:
        assert [c.name for c in step.children] == \
            ["serve.dispatch", "serve.fetch", "serve.check"]
        assert all(c.phase == "serve" for c in step.children)
        assert sum(c.duration for c in step.children) <= step.duration
    # each tile's progress record follows its step
    names = [s.name for s in tracer.spans]
    assert names.count("serve.journal") == 3
    assert names[:2] == ["step", "serve.journal"]


def test_scheduler_spans_keep_no_state_on_the_private_tracer(tmp_path):
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        svc = _served(tmp_path, 11)
    finally:
        jax.profiler.stop_trace()
    tracer = svc.scheduler.monitor.tracer
    assert [s.name for s in tracer.spans] == ["step"] * 3
    assert all(not s.children for s in tracer.spans)
    names = _host_event_names(str(tmp_path / "trace"))
    assert {"serve.dispatch", "serve.fetch", "serve.check",
            "serve.journal"} <= names


# --------------------------------------------------------------------------
# the run report
# --------------------------------------------------------------------------
def test_report_keeps_prep_beside_the_compile_window():
    cfg = ExecConfig(obs=ObsConfig(enabled=True))
    rng = np.random.default_rng(12)
    x, y = [Workspace.from_features(rng.random((39, 6)).astype(np.float32),
                                    config=cfg) for _ in range(2)]
    x.mantel(y, permutations=24, key=KEY)          # n=39: a new program
    report = x.report()
    assert all(set(v) == {"traces", "programs"}
               for v in report.compile.values())
    assert report.prep["seconds"] > 0
    assert report.to_dict()["prep"] == report.prep
