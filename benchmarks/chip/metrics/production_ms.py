"""Device milliseconds of distance production per study: the summed
device time of the production programs (``jit__panel_stats``, one row
strip of distances with its row sums) over the studies in the traced
window."""

PROGRAM = r"^jit__panel_stats\b"


def read(trace, facts, peaks):
    seconds = trace.module_s(PROGRAM)
    studies = facts.get("studies", 0)
    if seconds <= 0 or not studies:
        return None
    return 1000.0 * seconds / studies
