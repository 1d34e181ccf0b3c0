"""The null-distribution programs' share of their roofline: the least
time the chip could take for the tests they ran, the larger of
operations over peak FLOP/s and bytes over peak bandwidth (counted by
``counts/<method>.py`` from the test's definition), over their device
time in the traced window, in percent."""

import importlib

PROGRAM = r"^jit__null_distribution\b"


def read(trace, facts, peaks):
    seconds = trace.module_s(PROGRAM)
    tests = facts.get("tests", [])
    if seconds <= 0 or not tests:
        return None
    least = 0.0
    for t in tests:
        c = importlib.import_module(f"benchmarks.chip.counts.{t['method']}")
        n, k = t["n"], t["permutations"]
        least += max(c.ops(n, k) / peaks["flops_per_s"],
                     c.bytes_moved(n, k) / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
