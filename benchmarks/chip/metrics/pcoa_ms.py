"""Device milliseconds of the PCoA solve per study: the ops that the
program put under its ``pcoa.solve`` scope in the matrix-free fsvd
program (``jit__randomized_eigh_matfree``: the sketch, its power
iterations and the projected eigensolve, each matvec of the condensed
operator inside), each program's whole executions' mean times its
executions a study as the program counts them
(``benchmarks/chip/perstudy.py``). Nothing is read from a program that
keeps no scope map, no such scope or no count."""

from benchmarks.chip.perstudy import scope_seconds

MODULE = "jit__randomized_eigh_matfree"


def read(trace, facts, peaks):
    got = scope_seconds(trace, MODULE, "pcoa.solve", "pcoa_ms",
                        facts.get("executions", {}).get(MODULE))
    return None if got is None else 1000.0 * got
