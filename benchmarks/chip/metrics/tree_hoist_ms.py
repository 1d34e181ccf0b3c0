"""Device milliseconds of the tree hoist per study: the ops that the
program put under its ``dist.tree_hoist`` scope (``jit__tree_hoist``:
a table's branch embedding, once per table, one program per width),
each program's whole executions' mean times its executions a study as
the program counts them (``benchmarks/chip/perstudy.py``). Nothing is
read from a program without the hoist."""

from benchmarks.chip.perstudy import scope_seconds

MODULE = "jit__tree_hoist"


def read(trace, facts, peaks):
    got = scope_seconds(trace, MODULE, "dist.tree_hoist", "tree_hoist_ms",
                        facts.get("executions", {}).get(MODULE))
    return None if got is None else 1000.0 * got
