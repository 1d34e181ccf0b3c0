"""The null draws' share of their roofline, in percent:
``perm_roofline_pct``'s least time for the tests in the window over the
device time of the ops that the program put under its ``perm.draws``
scope in ``jit__null_distribution`` (``benchmarks/chip/scopes.py``),
without the order generation, the hoist and the observed statistic that
share the module. Nothing is read where the program keeps no scope map,
where the map holds no ``perm.*`` scope at all, or where it names less
than 99% of the module's op time.

The split of the module by scope, the map's coverage, the op time named
by each source of a path (``scopes.SOURCES``) and the seconds the map
took to make go to standard error."""

import sys
import time

from benchmarks.chip import scopes
from benchmarks.chip.harness import metric_reader

MODULE = "jit__null_distribution"
PROGRAM = r"^jit__null_distribution\b"
COVERAGE = 0.99
SPLIT = ("perm.orders", "perm.hoist", "perm.draws",
         "index", "gather", "reduce")


def read(trace, facts, peaks):
    whole = metric_reader("perm_roofline_pct")(trace, facts, peaks)
    t0 = time.perf_counter()
    scope_of = scopes.program_scope_map(MODULE)
    made_s = time.perf_counter() - t0
    if whole is None or scope_of is None:
        return None
    if not any(part.startswith("perm.") for path, _ in scope_of.values()
               for part in path.split("/")):
        print(f"perm_draws_roofline_pct: the compiled {MODULE} carries no "
              f"perm.* scope; nothing read", file=sys.stderr)
        return None
    by, mapped, ops, sources = scopes.seconds_by_scope(
        trace, MODULE, scope_of, SPLIT)
    module_s = trace.module_s(PROGRAM)
    print(f"perm_draws_roofline_pct: map made in {made_s!r} s; "
          f"{MODULE} {module_s!r} s, its ops "
          f"{ops!r} s, mapped {mapped!r} s, by source {sources!r}, "
          f"by scope {by!r}", file=sys.stderr)
    if ops <= 0 or mapped < COVERAGE * ops or by["perm.draws"] <= 0:
        return None
    # whole is 100 * least / module_s
    return whole * module_s / by["perm.draws"]
