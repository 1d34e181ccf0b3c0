"""The readers built on the program's own instruments, on a hand-made
trace: device ops put down to the program's scopes
(``perm_draws_roofline_pct``) and the program's preparation counter
(``program_prep_s``)."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness, scopes  # noqa: E402
from benchmarks.chip.tracefile import Trace  # noqa: E402

PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1_000_000  # ns
FACTS = {"tests": [{"method": "mantel", "n": 2048, "permutations": 999}]}
DRAWS = ("jit(_null_distribution)/perm.draws/while/body/closed_call/"
         "jit(_permute_reduce_jit)/while/body/closed_call")
SCOPES = {                       # scope paths, as scopes.merged_scopes gives
    "fusion.1": ("jit(_null_distribution)/perm.orders/jit(argsort)",
                 "op_name"),
    "fusion.2": ("jit(_null_distribution)/perm.hoist", "op_name"),
    "while.3": ("jit(_null_distribution)/perm.draws", "op_name"),
    "fusion.4": (f"{DRAWS}/gather/jit(_take)", "callees"),
    "fusion.5": (f"{DRAWS}/index", "op_name"),
}


def made(extra_op=None):
    """A 100 ms window, one null-distribution program of 60 ms: orders
    4 ms, hoist 6 ms, then a 40 ms loop of draws holding a 30 ms gather
    and an 8 ms index step; a 10 ms program of another module."""
    ops = [("%fusion.1 = u32[999,2048] fusion(), kind=kLoop", 10 * MS,
            4 * MS),
           ("%fusion.2 = f32[2096128] fusion(%x)", 14 * MS, 6 * MS),
           ("%while.3 = (s32[]) while(%t), body=%b", 20 * MS, 40 * MS),
           ("%fusion.4 = f32[32,65536] fusion(%a)", 21 * MS, 30 * MS),
           ("%fusion.5 = s32[32,65536] fusion(%c)", 51 * MS, 8 * MS),
           ("%fusion.4 = f32[8] fusion(%q)", 80 * MS, 10 * MS)]
    if extra_op is not None:
        ops.append(extra_op)
    modules = [("jit__null_distribution(77)", 10 * MS, 60 * MS),
               ("jit__panel_stats(9)", 80 * MS, 10 * MS)]
    return Trace(ops, modules, [("bench.window", 0, 100 * MS)],
                 (0, 100 * MS))


@pytest.fixture
def program_map(monkeypatch):
    """The program's compiled texts, read as ``SCOPES``; the modules
    asked for."""
    from repro.obs.compile import sentinel
    asked = []

    def hlo_texts(module):
        asked.append(module)
        return ["HloModule"] if module == "jit__null_distribution" else []

    monkeypatch.setattr(sentinel, "hlo_texts", hlo_texts)
    monkeypatch.setattr(scopes, "merged_scopes",
                        lambda texts: dict(SCOPES) if texts else {})
    return asked


def test_ops_go_to_their_module_and_scope():
    t = made()
    names = [n for n, _, _ in scopes.module_ops(t, "jit__null_distribution")]
    assert names == ["fusion.1", "fusion.2", "while.3", "fusion.4",
                     "fusion.5"]
    by, mapped, ops, sources = scopes.seconds_by_scope(
        t, "jit__null_distribution", SCOPES,
        ("perm.orders", "perm.hoist", "perm.draws", "gather", "index"))
    assert by == {"perm.orders": pytest.approx(0.004),
                  "perm.hoist": pytest.approx(0.006),
                  "perm.draws": pytest.approx(0.040),  # nested ops once
                  "gather": pytest.approx(0.030),
                  "index": pytest.approx(0.008)}
    assert mapped == pytest.approx(0.050) and ops == pytest.approx(0.050)
    # the gather fusion took its path from its callees; the rest their own
    assert sources == {"op_name": pytest.approx(0.050),
                       "callees": pytest.approx(0.030),
                       "users": 0.0, "caller": 0.0}


def test_draws_roofline_divides_the_same_least_time_by_the_draws(
        program_map):
    t = made()
    whole = harness.metric_reader("perm_roofline_pct")(t, FACTS, PEAKS)
    got = harness.metric_reader("perm_draws_roofline_pct")(t, FACTS, PEAKS)
    m = 2048 * 2047 // 2
    least = max(2.0 * m * 999 / PEAKS["flops_per_s"],
                (2.0 * m * 4 + 4000) / PEAKS["hbm_bytes_per_s"])
    assert got == pytest.approx(100 * least / 0.040)
    assert got == pytest.approx(whole * 0.060 / 0.040)
    assert got >= whole
    assert program_map == ["jit__null_distribution"]


def test_draws_roofline_reads_nothing_when_the_map_falls_short(
        program_map):
    # a 2 ms op the map does not name: 50 of 52 ms mapped, under 99%
    unnamed = ("%copy.9 = f32[2] copy(%z)", 60 * MS, 2 * MS)
    reader = harness.metric_reader("perm_draws_roofline_pct")
    assert reader(made(unnamed), FACTS, PEAKS) is None
    # ... and reads again once the op is short enough to leave 99%
    tiny = ("%copy.9 = f32[2] copy(%z)", 61 * MS, MS // 2)
    assert reader(made(tiny), FACTS, PEAKS) is not None


def test_draws_roofline_reads_nothing_without_a_program_map(monkeypatch):
    from repro.obs.compile import sentinel
    monkeypatch.delattr(type(sentinel), "hlo_texts")
    reader = harness.metric_reader("perm_draws_roofline_pct")
    assert reader(made(), FACTS, PEAKS) is None


def test_draws_roofline_reads_nothing_when_the_map_cannot_be_made(
        monkeypatch, capsys):
    from repro.obs.compile import sentinel

    def fails(module):
        raise NotImplementedError("text view unsupported")

    monkeypatch.setattr(sentinel, "hlo_texts", fails)
    reader = harness.metric_reader("perm_draws_roofline_pct")
    assert reader(made(), FACTS, PEAKS) is None
    assert "no scope map" in capsys.readouterr().err


def test_draws_roofline_reads_nothing_from_a_map_without_perm_scopes(
        program_map, monkeypatch, capsys):
    # a compiled text whose op_names carry no perm.* scope, as an
    # executable of a scope-less tree would
    bare = {n: ("jit(_null_distribution)/while/body", src)
            for n, (_, src) in SCOPES.items()}
    monkeypatch.setattr(scopes, "merged_scopes",
                        lambda texts: dict(bare) if texts else {})
    reader = harness.metric_reader("perm_draws_roofline_pct")
    assert reader(made(), FACTS, PEAKS) is None
    assert "carries no perm.* scope" in capsys.readouterr().err


def test_merged_scopes_leave_out_names_the_texts_disagree_on():
    def text(path):
        return "\n".join([
            "HloModule jit_f",
            "ENTRY %main (x: f32[]) -> f32[] {",
            f'  %a = f32[] add(%x, %x), metadata={{op_name="{path}/add"}}',
            '  ROOT %b = f32[] negate(%a), '
            'metadata={op_name="jit(f)/n/neg"}',
            "}"])
    same = scopes.merged_scopes([text("jit(f)/s"), text("jit(f)/s")])
    assert same == {"a": ("jit(f)/s", "op_name"),
                    "b": ("jit(f)/n", "op_name")}
    assert scopes.merged_scopes([text("jit(f)/s"), text("jit(f)/t")]) == {
        "b": ("jit(f)/n", "op_name")}


def test_prep_seconds_is_the_program_counter(monkeypatch):
    from repro.obs.compile import sentinel
    monkeypatch.setattr(sentinel, "prep", lambda: {
        "seconds": 45.5, "compile": {"count": 3, "seconds": 40.0},
        "by_entry": {}})
    reader = harness.metric_reader("program_prep_s")
    assert reader(made(), {}, PEAKS) == 45.5
    monkeypatch.setattr(sentinel, "prep", lambda: {"seconds": 0.0,
                                                   "by_entry": {}})
    assert reader(made(), {}, PEAKS) is None
    monkeypatch.delattr(sentinel, "prep")            # the patch above
    monkeypatch.delattr(type(sentinel), "prep")      # and the method
    assert not hasattr(sentinel, "prep")
    assert reader(made(), {}, PEAKS) is None
