"""Device milliseconds per served tile: the summed device time of the
tile programs (``jit_tile_statistics``, one padded tile of permutation
orders through the engine) over their count in the traced window."""

PROGRAM = r"^jit_tile_statistics\b"


def read(trace, facts, peaks):
    events = trace.module_events(PROGRAM)
    if not events:
        return None
    return 1000.0 * sum(e - s for _, s, e in events) / 1e9 / len(events)
