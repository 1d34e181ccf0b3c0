"""The readers of ``pcoa_ms``, ``tree_hoist_ms``, ``tree_hoist_roofline_pct``
and ``unifrac_production_ms`` on a hand-made trace: ops under a scope of
a program's compiled HLO, read with the map of the program each
execution ran, each program's whole executions weighed by the
executions a study runs as the program counts them, from a whole trace
and from traces cut into the second study and into the first."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from benchmarks.chip import harness, perstudy  # noqa: E402
from benchmarks.chip.tracefile import Trace  # noqa: E402

PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1_000_000  # ns


def hlo(module, width, scoped, unscoped):
    """A compiled module's text: ``scoped`` fusions carry ``<scope>`` in
    their op_name, ``unscoped`` ones do not."""
    lines = [f"HloModule {module}", "",
             f"ENTRY %main.1 (p: f32[4,{width}]) -> f32[4,{width}] {{",
             f"  %p = f32[4,{width}]{{1,0}} parameter(0)"]
    for name, scope in scoped:
        lines.append(f"  %{name} = f32[4,{width}]{{1,0}} fusion(%p), "
                     f"kind=kLoop, calls=%c, metadata={{op_name="
                     f"\"jit(f)/outer/{scope}/abs\"}}")
    for name in unscoped:
        lines.append(f"  %{name} = f32[4,{width}]{{1,0}} fusion(%p), "
                     f"kind=kLoop, calls=%c, metadata={{op_name="
                     f"\"jit(f)/outer/slice\"}}")
    lines.append("}")
    return "\n".join(lines)


def op(name, width, start, dur):
    return (f"%{name} = f32[4,{width}]{{1,0}} fusion(f32[4,{width}]{{1,0}} "
            f"%p), kind=kLoop, calls=%c", start * MS, dur * MS)


# two programs of one module whose instruction names disagree: in the
# 86-wide program fusion.1 is under the scope, in the 90-wide one it is not
TEXTS = {
    "jit__panel_stats": {
        "w86": hlo("jit__panel_stats", 86, [("fusion.1", "dist.unifrac")],
                   ["fusion.2"]),
        "w90": hlo("jit__panel_stats", 90, [("fusion.2", "dist.unifrac")],
                   ["fusion.1"])},
    "jit__tree_hoist": {"h86": hlo("jit__tree_hoist", 86,
                                   [("fusion.1", "dist.tree_hoist")], [])},
    "jit__randomized_eigh_matfree": {
        "k10": hlo("jit__randomized_eigh_matfree", 10,
                   [("fusion.1", "pcoa.solve")], ["fusion.2"])},
}


def made(cut=None):
    """Two 28.5 ms studies in a 60 ms window. Each: the hoist (2 ms), the
    86-wide production (4 ms under the scope, 3 outside), the 90-wide
    one (5 under, 1 outside), the solve (6 under, 1 outside), and 0.5 ms
    on the host after its last op. Cut, the trace keeps device ops (and
    module executions) only where they start before ``cut`` ms."""
    ops, modules = [], []
    for base in (0, 30):
        modules += [("jit__tree_hoist(1)", base + 1, 2),
                    ("jit__panel_stats(2)", base + 4, 7),
                    ("jit__panel_stats(3)", base + 13, 6),
                    ("jit__randomized_eigh_matfree(4)", base + 21, 7)]
        ops += [op("fusion.1", 86, base + 1, 2),
                op("fusion.1", 86, base + 4, 4),
                op("fusion.2", 86, base + 8, 3),
                op("fusion.2", 90, base + 13, 5),
                op("fusion.1", 90, base + 18, 1),
                op("fusion.1", 10, base + 21, 6),
                op("fusion.2", 10, base + 27, 1)]
    if cut is not None:
        ops = [o for o in ops if o[1] < cut * MS]
        modules = [m for m in modules if m[1] < cut]
    modules = [(n, s * MS, d * MS) for n, s, d in modules]
    spans = [("bench.window", 0, 60 * MS), ("bench.study", 0, 28.5 * MS),
             ("bench.study", 30 * MS, 28.5 * MS)]
    return Trace(ops, modules, spans, (0, 60 * MS))


@pytest.fixture
def programs(monkeypatch):
    from repro.obs.compile import sentinel
    monkeypatch.setattr(sentinel, "compiled",
                        lambda module, scope=None: TEXTS.get(module.split("(")[0], {}))


FACTS = {"studies": 2, "tree_hoists": [[4, 43, 86]],
         "executions": {"jit__tree_hoist": {"h86": 1},
                        "jit__panel_stats": {"w86": 1, "w90": 1},
                        "jit__randomized_eigh_matfree": {"k10": 1}}}
ROOF = 100 * (4.0 * 4 * 43 + 4.0 * 4 * 86) / 819e9 / 0.002


@pytest.mark.parametrize("cut, name, want", [
    # whole, cut into the second study, and cut inside the first after
    # production: every program that ran has a whole execution kept
    (None, "unifrac_production_ms", 9.0), (52, "unifrac_production_ms", 9.0),
    (20, "unifrac_production_ms", 9.0),
    (None, "tree_hoist_ms", 2.0), (52, "tree_hoist_ms", 2.0),
    (20, "tree_hoist_ms", 2.0),
    (None, "tree_hoist_roofline_pct", ROOF),
    (52, "tree_hoist_roofline_pct", ROOF),
    (None, "pcoa_ms", 6.0), (52, "pcoa_ms", 6.0),
    # cut inside the first study's 90-wide production, and before its
    # solve: a program that ran has no whole execution, so nothing is read
    (12, "unifrac_production_ms", None), (12, "tree_hoist_ms", 2.0),
    (20, "pcoa_ms", None),
])
def test_readers_per_study(programs, cut, name, want):
    got = harness.metric_reader(name)(made(cut), FACTS, PEAKS)
    assert got == (None if want is None else pytest.approx(want))


def test_each_program_is_weighed_by_its_executions(programs):
    """Three 86-wide panels and one 90-wide panel a study: 3·4 + 1·5 ms."""
    facts = dict(FACTS, executions=dict(
        FACTS["executions"], jit__panel_stats={"w86": 3, "w90": 1}))
    got = harness.metric_reader("unifrac_production_ms")(made(52), facts,
                                                         PEAKS)
    assert got == pytest.approx(17.0)


def test_executions_a_study_come_from_the_programs_count(monkeypatch):
    from repro.obs.compile import sentinel
    counts = {"jit_f": {("a",): 2}}
    monkeypatch.setattr(sentinel, "runs",
                        lambda module, scope=None: dict(counts.get(module, {})))
    before = perstudy.runs(["jit_f", "jit_g"])
    counts["jit_f"] = {("a",): 8, ("b",): 3}
    after = perstudy.runs(["jit_f", "jit_g"])
    assert perstudy.per_study(before, after, 3) == {
        "jit_f": {("a",): 2.0, ("b",): 1.0}, "jit_g": {}}
    assert perstudy.per_study(before, after, 0) == {}
    monkeypatch.delattr(sentinel, "runs")
    monkeypatch.delattr(type(sentinel), "runs")
    assert perstudy.runs(["jit_f"]) == {}


@pytest.mark.parametrize("name", ["unifrac_production_ms", "tree_hoist_ms",
                                  "pcoa_ms", "tree_hoist_roofline_pct"])
def test_nothing_is_read_without_the_scope(monkeypatch, name):
    """A program without the scope (the parent of the change that added
    it), without any compiled text or without a count of executions
    gives nothing, and raises nothing."""
    from repro.obs.compile import sentinel
    monkeypatch.setattr(sentinel, "compiled", lambda module, scope=None: {
        "any": hlo(module, 86, [], ["fusion.1", "fusion.2"])})
    assert harness.metric_reader(name)(made(), FACTS, PEAKS) is None
    monkeypatch.setattr(sentinel, "compiled",
                        lambda module, scope=None: TEXTS.get(module.split("(")[0], {}))
    assert harness.metric_reader(name)(
        made(), dict(FACTS, executions={}), PEAKS) is None
    monkeypatch.setattr(sentinel, "compiled", lambda module, scope=None: {})
    assert harness.metric_reader(name)(made(), FACTS, PEAKS) is None
    monkeypatch.delattr(sentinel, "compiled")
    monkeypatch.delattr(type(sentinel), "compiled")
    assert harness.metric_reader(name)(made(), FACTS, PEAKS) is None
