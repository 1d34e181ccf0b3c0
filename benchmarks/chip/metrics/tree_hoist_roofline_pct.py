"""The tree hoist's share of its roofline, in percent: the least time
for a study's hoist bytes at the chip's bandwidth (read each (n, T)
table, write each (n, B) embedding, 4 bytes an entry:
``counts/unifrac.py``; the tables from the run's facts,
``tree_hoists``) over a study's device time of the ops under the
``dist.tree_hoist`` scope, as ``tree_hoist_ms`` reads it."""

from benchmarks.chip.counts.unifrac import hoist_bytes
from benchmarks.chip.perstudy import scope_seconds

MODULE = "jit__tree_hoist"


def read(trace, facts, peaks):
    hoists = facts.get("tree_hoists")
    if not hoists:
        return None
    got = scope_seconds(trace, MODULE, "dist.tree_hoist",
                        "tree_hoist_roofline_pct",
                        facts.get("executions", {}).get(MODULE))
    if got is None:
        return None
    least = sum(hoist_bytes(*h) for h in hoists) / peaks["hbm_bytes_per_s"]
    return 100.0 * least / got
