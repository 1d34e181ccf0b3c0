"""Permuted pairs per second of device time in the null-distribution
programs (``jit__null_distribution``, the engine's whole-test program):
real draws times pairs m, over the summed device time of those programs
in the traced window."""

PROGRAM = r"^jit__null_distribution\b"


def read(trace, facts, peaks):
    seconds = trace.module_s(PROGRAM)
    pairs = sum(t["permutations"] * t["n"] * (t["n"] - 1) // 2
                for t in facts.get("tests", []))
    if seconds <= 0 or not pairs:
        return None
    return pairs / seconds
