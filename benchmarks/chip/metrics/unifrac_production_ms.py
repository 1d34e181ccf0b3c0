"""Device milliseconds of UniFrac production per study: the ops that the
program put under the ``dist.unifrac`` scope of the metric's
``accumulate`` in the production programs (``jit__panel_stats``, one
row panel of distances each: the per-chunk |a − b| and max(a, b) sums
over the branch embedding; one program per table width), each
program's whole executions' mean times its executions a study as the
program counts them (``benchmarks/chip/perstudy.py``). The chunks'
slicing and the finish lie outside that scope."""

from benchmarks.chip.perstudy import scope_seconds

MODULE = "jit__panel_stats"


def read(trace, facts, peaks):
    got = scope_seconds(trace, MODULE, "dist.unifrac",
                        "unifrac_production_ms",
                        facts.get("executions", {}).get(MODULE))
    return None if got is None else 1000.0 * got
