"""The trace → metric reduction, on a hand-made trace and on a slice of
one recorded on a TPU v5e (``data/served_trace.json.gz``)."""

import math
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip.tracefile import Trace  # noqa: E402

RECORDED = Path(__file__).parent / "data" / "served_trace.json.gz"
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1_000_000  # ns


def made():
    """A 100 ms window: two programs, overlapping ops, host spans."""
    ops = [("fusion.1", 10 * MS, 20 * MS),      # 10-30
           ("fusion.2", 25 * MS, 15 * MS),      # 25-40, overlaps
           ("copy", 60 * MS, 10 * MS),          # 60-70
           ("fusion.1", 95 * MS, 10 * MS)]      # 95-105, clipped to 100
    modules = [("jit__null_distribution(123)", 10 * MS, 30 * MS),
               ("jit__panel_stats(9)", 60 * MS, 10 * MS),
               ("jit_tile_statistics(4)", 95 * MS, 10 * MS)]
    spans = [("bench.window", 0, 100 * MS),
             ("bench.study", 0, 50 * MS),
             ("bench.mantel", 5 * MS, 45 * MS),
             ("bench.workspace", 50 * MS, 30 * MS)]
    return Trace(ops, modules, spans, (0, 100 * MS))


def test_busy_is_the_union_of_ops_inside_the_window():
    t = made()
    assert t.busy_intervals() == [[10 * MS, 40 * MS], [60 * MS, 70 * MS],
                                  [95 * MS, 100 * MS]]
    assert t.busy_s() == pytest.approx(0.045)
    assert t.window_s == pytest.approx(0.1)
    assert t.idle_share() == pytest.approx(0.55)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    gaps = dict(made().idle_gaps())
    # 0-10 inside bench.mantel (inside bench.study); 40-60 has its middle
    # in bench.workspace; 70-95 under no span but the window
    assert gaps == {"bench.mantel (1 gaps)": pytest.approx(0.010),
                    "bench.workspace (1 gaps)": pytest.approx(0.020),
                    "no benchmark span (1 gaps)": pytest.approx(0.025)}
    assert sum(gaps.values()) == pytest.approx(made().window_s
                                               - made().busy_s())


def test_top_ops_sum_by_name_and_sort():
    top = made().top_ops()
    assert top == [["jit__null_distribution/fusion.1", pytest.approx(0.020)],
                   ["jit__null_distribution/fusion.2", pytest.approx(0.015)],
                   ["jit__panel_stats/copy", pytest.approx(0.010)],
                   ["jit_tile_statistics/fusion.1", pytest.approx(0.005)]]


@pytest.mark.parametrize("name, facts, want", [
    ("perm_pairs_per_s",
     {"tests": [{"method": "mantel", "n": 2048, "permutations": 999}]},
     999 * 2048 * 2047 // 2 / 0.030),
    ("production_ms", {"studies": 2}, 5.0),
    ("device_idle_pct.library", {}, 55.0),
    ("device_idle_pct.served", {}, 55.0),
    ("tile_device_ms.served", {}, 5.0),
])
def test_readers_on_the_made_trace(name, facts, want):
    assert harness.metric_reader(name)(made(), facts, PEAKS) == \
        pytest.approx(want)


def test_roofline_share_from_the_counts():
    facts = {"tests": [{"method": "mantel", "n": 2048, "permutations": 999}]}
    m = 2048 * 2047 // 2
    least = max(2.0 * m * 999 / PEAKS["flops_per_s"],
                (2.0 * m * 4 + 4000) / PEAKS["hbm_bytes_per_s"])
    got = harness.metric_reader("perm_roofline_pct")(made(), facts, PEAKS)
    assert got == pytest.approx(100 * least / 0.030)


@pytest.mark.parametrize("name", ["perm_pairs_per_s", "perm_roofline_pct",
                                  "production_ms", "tile_device_ms.served"])
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    empty = Trace([], [], [("bench.window", 0, MS)], (0, MS))
    assert harness.metric_reader(name)(empty, {"tests": [], "studies": 0},
                                       PEAKS) is None


def test_round_trip_through_json(tmp_path):
    t = made()
    path = str(tmp_path / "t.json.gz")
    t.to_json(path)
    u = Trace.from_json(path)
    assert u.busy_intervals() == t.busy_intervals()
    assert u.idle_gaps() == t.idle_gaps()


def test_recorded_served_trace():
    """A slice of a served window on a TPU v5e: tile programs, their
    ops, the benchmark's host spans."""
    t = Trace.from_json(str(RECORDED))
    assert t.modules and t.ops and t.spans
    idle = t.idle_share()
    assert 0.0 < idle < 1.0
    assert sum(s for _, s in t.idle_gaps(count=10 ** 6)) == \
        pytest.approx(t.window_s - t.busy_s(), rel=1e-9)
    tiles = t.module_events(r"^jit_tile_statistics\b")
    assert tiles
    ms = harness.metric_reader("tile_device_ms.served")(t, {}, PEAKS)
    assert math.isfinite(ms) and ms > 0
    assert ms == pytest.approx(1000 * t.module_s(r"^jit_tile_statistics\b")
                               / len(tiles))
    # every tile program overlaps the device's busy time
    busy = t.busy_intervals()
    for _, s, e in tiles:
        assert any(b0 < e and s < b1 for b0, b1 in busy)
